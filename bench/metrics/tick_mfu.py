"""Model operations of the tokens the ticks emitted, per tick, over the
tick program's device time times the chip's bf16 peak, in percent: 2 per
matmul weight per token, plus attention over each token's live context."""
from bench import counts


def read(rec):
    tr, s, peaks, w = rec["trace"], rec["shapes"], rec["peaks"], rec["window"]
    tick = (tr or {}).get("programs", {}).get("tick")
    if not tick or not tick["n"] or not peaks or not w["decode_calls"]:
        return None
    ops = counts.decode_flops(s, sum(r["tick_tokens"] for r in w["requests"]),
                              sum(r["context"] for r in w["requests"]))
    per_tick_s = tick["s_whole"] / tick["n"]
    return 100.0 * ops / w["decode_calls"] / (per_tick_s * peaks["bf16_flops"])
