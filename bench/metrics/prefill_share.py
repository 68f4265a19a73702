"""Admission: device time of admission prefill (``jit_prefill_fn``) and
of the cache-row and round-state writes (``jit_write_fn``) over the
traced window, in percent."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["window_s"]:
        return None
    t = tr["programs"].get("jit_prefill_fn", 0.0) + tr["programs"].get(
        "jit_write_fn", 0.0)
    if not t:
        return None
    return 100.0 * t / tr["window_s"]
