"""mlp_ms.train: device ms per window step of the ops under the program's
``mlp`` named scope: the SwiGLU MLP's matmuls and activation, forward,
recomputed forward and backward, with the next norm's backward fused in
(``xtrace.reduce``)."""

from chipbench import xtrace


def read(out):
    return xtrace.scope_ms(out.get("trace"), "mlp")
