"""hbm_peak_frac.train: the compiled train step's footprint over the
device's ``bytes_limit``, in %: arguments, outputs that do not reuse a
donated argument, and temporaries (activations, gradients), as the
compiler's memory analysis gives them. This is the headroom that bounds
the batch; the runtime's ``peak_bytes_in_use`` leaves the step's
temporaries out on the TPU."""


def read(out):
    if not out.get("step_bytes") or not out.get("bytes_limit"):
        return None
    return 100.0 * out["step_bytes"] / out["bytes_limit"]
