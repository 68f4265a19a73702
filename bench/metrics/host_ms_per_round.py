"""Round loop (host): host bookkeeping per round, device waits left out
(``RolloutStats.host_time_s`` over ``n_rounds``), in milliseconds."""


def read(run):
    if not run["rounds"]:
        return None
    return 1000.0 * run["host_time_s"] / run["rounds"]
