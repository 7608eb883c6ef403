"""attention_ms.train: device ms per window step of the ops under the
program's ``attention`` named scope: the q/k/v/o projections, RoPE and
the attention core (the ``flash`` kernel, where it runs, with its layout
copies), forward, recomputed forward and backward (``xtrace.reduce``)."""

from chipbench import xtrace


def read(out):
    return xtrace.scope_ms(out.get("trace"), "attention")
