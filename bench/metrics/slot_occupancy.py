"""Scheduler: mean active rows over slots, across the window's rounds
(``RolloutStats.effective_batch``), in percent."""


def read(run):
    eff = run["effective_batch"]
    if not eff:
        return None
    return 100.0 * sum(eff) / (len(eff) * run["slots"])
