"""device_idle.train: the share of the traced window with no operation on
the device, in %: 100 (1 - busy / window), from the profiler trace
(``xtrace.py``)."""


def read(out):
    trace = out.get("trace")
    if not trace or "flops_per_token" not in out:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
