"""Roofline share of the packed 3-bit matmul kernel (``kernels/qmatvec``)
inside decode tick programs, in percent: the least time the chip needs for
those calls (each the larger of 2*M*K*N over the bf16 peak and its bytes,
``qp`` words plus activations, over HBM bandwidth) over their measured
device time. M is the slot count, which every tick computes."""
from bench import counts


def read(rec):
    tr, s, peaks = rec["trace"], rec["shapes"], rec["peaks"]
    k = (tr or {}).get("kernels", {}).get("tick:qmatvec")
    if not k or not k["n"] or not peaks:
        return None
    per_call = len(s.matrices())
    layers_run = k["n"] / per_call
    need = layers_run * counts.qmatvec_roofline_s(s, rec["serve"]["slots"],
                                                  peaks)
    return 100.0 * need / k["s"]
