"""flash_attention_roofline: the ops under the program's ``flash`` named
scope, as a share of the chip's bf16 peak, in %.

Work: the causal model work of attention whatever implements it, three
times the forward's Q.K and P.V products (``flops.mixer_flops_per_token``:
forward once, backward twice), for every token of the window's steps.
Nothing is counted for the recomputed forward, recomputed scores or
masked tiles. Time: the device self time of the ops in scope ``flash``
on every device (the kernel's forward, dQ and dK/dV calls and their
layout copies), times the peak for the run's ``device_kind``
(``peaks.py``). Reads nothing where no op carries the scope.
"""

from chipbench import flops, peaks


def read(out):
    trace = out.get("trace")
    if not trace or "flash" not in trace["scopes"] or not trace["steps"]:
        return None
    tr = out["traffic"]
    work = (3.0 * flops.mixer_flops_per_token(out["config"], tr["seq"])
            * tr["batch"] * tr["seq"] * trace["steps"])
    busy = trace["scopes"]["flash"] * trace["devices"]
    return 100.0 * work / (busy * peaks.peak(out["device_kind"])["bf16_flops"])
