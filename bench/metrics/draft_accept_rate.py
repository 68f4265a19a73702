"""Draft: accepted over drafted tokens in the window (``RolloutStats``),
in percent."""


def read(run):
    if not run["drafted"]:
        return None
    return 100.0 * run["accepted"] / run["drafted"]
