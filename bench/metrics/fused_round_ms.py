"""Fused round (device): device time of the fused round program
(``jit_fused``) over the profiled rounds, in milliseconds. The trace
reduction has checked that the profile holds one execution per round."""


def read(run):
    tr, st = run["trace"], run.get("traced")
    if not tr or not st or not st["rounds"]:
        return None
    t = tr["programs"].get("jit_fused")
    if not t:
        return None
    return 1000.0 * t / st["rounds"]
