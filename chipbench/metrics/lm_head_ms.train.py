"""lm_head_ms.train: device ms per window step of the ops under the
program's ``lm_head`` named scope: the vocabulary projection and the fp32
cross-entropy, forward and backward (``xtrace.reduce``)."""

from chipbench import xtrace


def read(out):
    return xtrace.scope_ms(out.get("trace"), "lm_head")
