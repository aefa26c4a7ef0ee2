"""Roofline share of the packed 3-bit matmul kernel (``kernels/qmatvec``)
inside admission prefill programs, in percent: the least time the chip
needs for those calls (each the larger of 2*M*K*N over the bf16 peak and
its bytes, ``qp`` words plus activations, over HBM bandwidth) over their
measured device time, over every prefill wholly inside the traced window.
M is slots x bucket, the rows each prefill computes, with the bucket read
from the ``serve.admit`` span that dispatched it."""
from bench import counts


def read(rec):
    tr, s, peaks = rec["trace"], rec["shapes"], rec["peaks"]
    runs = [r for r in (tr or {}).get("prefill_runs") or ()
            if r["kernels"].get("qmatvec", {}).get("n")]
    if not runs or not peaks:
        return None
    per_call = len(s.matrices())
    slots = rec["serve"]["slots"]
    need = sum(r["kernels"]["qmatvec"]["n"] / per_call
               * counts.qmatvec_roofline_s(s, slots * r["bucket"], peaks)
               for r in runs)
    return 100.0 * need / sum(r["kernels"]["qmatvec"]["s"] for r in runs)
