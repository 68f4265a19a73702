"""Round loop (host): verify rounds per RL step in the window
(``RolloutStats.n_rounds`` over steps)."""


def read(run):
    if not run["steps"]:
        return None
    return run["rounds"] / run["steps"]
