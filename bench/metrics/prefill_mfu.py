"""Model operations of the prompts that the admission prefills wholly
inside the traced window admitted, over those programs' device time times
the chip's bf16 peak, in percent (device). Only the prompts' own tokens
count: the padding rows and positions that a prefill of slots x bucket
rows computes are not model work (``prefill_row_use`` is their useful
share). The prompts are those of the requests admitted after the trace
began (``traced_from_s``); the run waits for the device at both ends of
the trace, so each of their prefills lies inside it. None where their
count differs from the rows of the traced prefills' admissions."""
from bench import counts


def read(rec):
    tr, s, peaks = rec["trace"], rec["shapes"], rec["peaks"]
    runs = [r for r in (tr or {}).get("prefill_runs") or () if r["s"] > 0]
    t0 = rec.get("traced_from_s")
    if not runs or not peaks or t0 is None:
        return None
    lens = [r["prompt_len"] for r in rec["window"]["requests"]
            if r.get("admitted") is not None and r["admitted"] >= t0]
    if len(lens) != sum(r["rows"] for r in runs):
        return None
    ops = sum(counts.prompt_flops(s, n) for n in lens)
    return 100.0 * ops / (sum(r["s"] for r in runs) * peaks["bf16_flops"])
