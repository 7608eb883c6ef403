"""optimizer_ms.train: device ms per window step of the ops under the
program's ``optimizer`` named scope: the global-norm clip and the AdamW
update of the fp32 master weights and moments (``xtrace.reduce``)."""

from chipbench import xtrace


def read(out):
    return xtrace.scope_ms(out.get("trace"), "optimizer")
