"""End to end: the window's wall time over its RL steps, in seconds. A
step ends when its last rollout's last token is on the host."""


def read(run):
    if not run.get("steps"):
        return None
    return run["window_s"] / run["steps"]
