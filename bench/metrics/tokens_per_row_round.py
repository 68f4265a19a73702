"""Draft: tokens emitted per row per round, the window's emitted tokens
over the sum of its requests' rounds (``Request.rounds``)."""


def read(run):
    if not run["request_rounds"]:
        return None
    return run["emitted_tokens"] / run["request_rounds"]
