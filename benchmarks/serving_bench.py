"""Serving throughput vs slot count: the paper's weight-streaming
amortization curve, measured — under MIXED-LENGTH traffic.

The paper's Fig. 4 dataflow streams quantized weights once per step
regardless of batch size, so tokens/sec should rise near-linearly with the
number of co-resident decode slots until compute saturates. This benchmark
drives the batched continuous-batching engine over a fixed mixed-length
request set (prompts spanning both admission buckets, the heavy-traffic
shape the bucketed prefill path exists for) at several slot counts and
reports tokens/sec per weight form (float ``w``, int8 levels ``q``, packed
3-bit containers ``qp`` — the deployed form, where the per-tick cost is
dominated by the batch-independent container unpack and the amortization is
strongest), plus admission throughput: batched ``prefills`` issued and
prompt tokens/sec (``ptok/s``) absorbed through them. Non-monotonic tok/s
points are usually an admission effect: more slots means fewer, larger
batched prefills. ``--matmul-mode`` selects the quantized-matmul dispatch
(auto/kernel/dequant; kernel is interpret-mode off-TPU), ``--attn-mode``
the decode-attention dispatch (auto/kernel/ref — the fused Pallas
``attn_decode`` kernel vs the einsum path), and ``--kv8`` serves from an
int8 KV cache; every row reports the shared-cache bytes per slot, which
kv8 halves (twice the slots per fixed cache budget).

``--mix long`` swaps the short-prompt traffic for 1k–4k-token prompts
(admission buckets 1024/2048/4096), the regime where prefill attention
dominates admission cost: the einsum path materializes an O(T^2) fp32 score
tensor per sequence while the blocked Pallas kernel (``--attn-mode
kernel``) keeps one (bt, G, bs) tile in VMEM — the ``ptok/s`` column is
the number that moves. The long mix defaults to fewer slots/requests, one
repeat and a 256-token ``--attn-chunk`` (caps the ref-mode chunked-prefill
working set; the engine threads it through to ``chunked_attention``).

``--spec-k K`` adds the speculative-serving axis: a packed-3-bit drafter
derived from the same checkpoint (``api.draft_of``; ``--draft-depth`` for
the half-depth variant) proposes K tokens per tick and the swept form
verifies them in one multi-token pass. The ``acc/tick`` column reports
tokens committed per slot-tick (exactly 1.0 without speculation — the
tokens-per-tick multiplier is the whole point), plus the drain-synced
``spec_accept_rate`` in the artifact; ``--check`` then gates on
accepted-tokens-per-tick > 1 in every swept cell instead of the qp
monotonicity curve.

``--mix crash`` exercises the durability layer instead of the
amortization curve: each cell runs with periodic snapshots + a write-ahead
journal, injects a process kill mid-run (``FaultPlan.crash_at_tick``),
then recovers a FRESH engine — restore the latest snapshot, replay the
journal tail — and finishes the workload. Reported per cell: snapshot
step restored, journal events replayed, requests resubmitted, restore
seconds, and the zero-loss verdict (pre-crash drains + recovered outputs
token-identical to an uncrashed reference at T=0). ``--check`` gates on
zero accepted-token loss AND an actual snapshot restore in every cell;
the artifact goes to ``BENCH_serving_durability.json`` by default.

Results are also written as a JSON artifact (default ``BENCH_serving.json``)
so CI can archive the perf trajectory.

    PYTHONPATH=src python benchmarks/serving_bench.py
    PYTHONPATH=src python benchmarks/serving_bench.py --check   # CI gate

Runs on CPU in a couple of minutes at the default reduced size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.models import get_model
from repro.serving.engine import ServingEngine

MIXES = {
    # short prompts cycling over both small admission buckets (<=8, 9..16)
    "mixed": [3, 8, 5, 12, 4, 16, 7, 9],
    # 1k-4k prompts (buckets 1024/2048/4096): admission time is dominated
    # by prefill attention, the regime the blocked kernel exists for
    "long": [1024, 2048, 1536, 4096],
    # overload: same short prompts, but arrival-paced at ~2x the engine's
    # slot-tick service capacity under bounded admission + mixed deadlines
    # + preemption — measures shed/deadline-miss/latency, not amortization
    "overload": [3, 8, 5, 12, 4, 16, 7, 9],
    # crash: kill the engine mid-run, recover a FRESH engine from the
    # latest snapshot + journal tail — measures restore/replay cost and
    # verifies zero accepted-token loss (recovered == uncrashed at T=0)
    "crash": [3, 8, 5, 12, 4, 16, 7, 9],
}
# per-mix defaults for the knobs whose sensible values depend on prompt
# scale: (slots, requests, max_new, repeats, attn_chunk)
MIX_DEFAULTS = {
    "mixed": ("1,4,8,16", 16, 24, 3, 1024),
    "long": ("1,2", 4, 8, 1, 256),
    "overload": ("2,4", 24, 12, 1, 1024),
    "crash": ("2,4", 12, 12, 1, 1024),
}


def _prompts(requests: int, lengths):
    return [[(i * 7 + j) % 50 + 1
             for j in range(lengths[i % len(lengths)])]
            for i in range(requests)]


def _engine(params, cfg, policy, slots, max_prompt, max_new,
            matmul_mode="auto", attn_mode="auto", kv_bits=None, spec_k=0,
            draft=None, attn_chunk=1024):
    return ServingEngine(params, cfg, policy=policy, slots=slots,
                         max_len=max_prompt + max_new + 1 + spec_k,
                         dtype=jnp.float32, matmul_mode=matmul_mode,
                         attn_mode=attn_mode, kv_bits=kv_bits,
                         spec_k=spec_k,
                         draft_params=draft[1] if draft else None,
                         draft_cfg=draft[0] if draft else None,
                         attn_chunk=attn_chunk)


def _cache_bytes_per_slot(eng: ServingEngine) -> int:
    """Shared-cache bytes divided by slots — the number kv_bits=8 halves
    (KV entries go bf16/f32 -> int8 + one fp32 scale per token)."""
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(eng.cache))
    return total // eng.slots


def bench_form(params, cfg, policy, *, slots: int, requests: int,
               max_new: int, lengths, repeats: int = 3,
               matmul_mode: str = "auto", attn_mode: str = "auto",
               kv_bits=None, spec_k: int = 0, draft=None,
               attn_chunk: int = 1024) -> dict:
    # warmup on the SAME engine instance that gets timed: the jitted
    # prefill/tick closures are per-engine, so a throwaway warmup engine
    # would leave the timed run paying compile time. One prompt per
    # admission bucket the mix touches compiles every batched-prefill entry.
    eng = _engine(params, cfg, policy, slots, max(lengths), max_new,
                  matmul_mode, attn_mode, kv_bits, spec_k, draft, attn_chunk)
    for bucket in sorted({eng._bucket_len(n) for n in lengths}):
        eng.submit([1] * bucket, max_new=max_new)
    eng.run_all()

    # best-of-N: CPU wall-clock noise (scheduler, allocator) easily exceeds
    # the 4->8-slot amortization step on sub-second runs; min time is the
    # standard denoiser
    prompts = _prompts(requests, lengths)
    ptoks = sum(len(p) for p in prompts)
    best = None
    for _ in range(repeats):
        ticks0, prefills0 = eng.decode_calls, eng.prefill_calls
        for p in prompts:
            eng.submit(p, max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run_all()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        ticks = eng.decode_calls - ticks0
        # per-slot speculative win: decode-emitted tokens per request tick
        # (the admission sample rides prefill, so it is excluded). Exactly
        # 1.0 without speculation; 1 + mean accepted drafts with it.
        slot_ticks = sum(r.ticks for r in done)
        dec_toks = sum(len(r.out) - 1 for r in done)
        r = {"slots": slots, "tokens": toks, "secs": dt,
             "tok_per_sec": toks / dt, "ticks": ticks,
             "prefills": eng.prefill_calls - prefills0,
             "prompt_tokens": ptoks, "prompt_tok_per_sec": ptoks / dt,
             "attn_mode": attn_mode, "kv_bits": kv_bits,
             "spec_k": spec_k,
             "accepted_tok_per_tick": dec_toks / max(slot_ticks, 1),
             "spec_accept_rate": eng.spec_accept_rate,
             "cache_bytes_per_slot": _cache_bytes_per_slot(eng)}
        if best is None or r["tok_per_sec"] > best["tok_per_sec"]:
            best = r
    return best


def bench_overload(params, cfg, policy, *, slots: int, requests: int,
                   max_new: int, lengths, matmul_mode: str = "auto",
                   attn_mode: str = "auto", kv_bits=None,
                   attn_chunk: int = 1024, max_ticks: int = 4096) -> dict:
    """Overload scenario: requests arrive in waves of ``2 * slots`` every 4
    ticks — roughly 2x the slot-tick service rate, so the bounded queue
    (``queue_limit = 2 * slots``, reject policy) must shed and the
    fair-share preemption/deadline machinery is exercised, not idle.
    Deadlines cycle none / loose (4 * max_new) / tight (max_new // 2), so a
    fraction of requests CANNOT finish in time by construction. Reports
    shed-rate, deadline-miss-rate, preemption count and submit->finish
    latency percentiles; ``deadlocked`` records whether the watchdog fired
    (the --check gate requires it never does)."""
    from repro.serving.resilience import WatchdogExpired
    eng = ServingEngine(params, cfg, policy=policy, slots=slots,
                        max_len=max(lengths) + max_new + 1,
                        dtype=jnp.float32, matmul_mode=matmul_mode,
                        attn_mode=attn_mode, kv_bits=kv_bits,
                        attn_chunk=attn_chunk,
                        queue_limit=2 * slots, shed_policy="reject",
                        preempt_after=max(2, max_new // 4),
                        max_ticks=max_ticks)
    prompts = _prompts(requests, lengths)
    deadlines = [None, 4 * max_new, max(1, max_new // 2)]
    outcomes, done = [], []
    deadlocked = False
    t0 = time.perf_counter()
    wave = 2 * slots
    for i in range(0, len(prompts), wave):
        for j, p in enumerate(prompts[i:i + wave]):
            outcomes.append(eng.submit(
                p, max_new=max_new,
                deadline_ticks=deadlines[(i + j) % len(deadlines)]))
        for _ in range(4):                 # serve between arrival waves
            eng.step()
        done.extend(eng.drain())
    try:
        done.extend(eng.run_all())
    except WatchdogExpired:
        deadlocked = True
        done.extend(eng.drain())
    dt = time.perf_counter() - t0
    accepted = sum(1 for o in outcomes if o.accepted)
    lats = sorted(r.finish_time - r.submit_time for r in done
                  if r.submit_time and r.finish_time)
    pct = (lambda q: lats[min(len(lats) - 1, int(q * len(lats)))]) if lats \
        else (lambda q: 0.0)
    toks = sum(len(r.out) for r in done)
    return {"slots": slots, "submitted": len(outcomes), "accepted": accepted,
            "completed_ok": sum(1 for r in done if r.status == "ok"),
            "shed_rate": eng.shed_count / max(len(outcomes), 1),
            "deadline_miss_rate": eng.deadline_miss_count / max(accepted, 1),
            "preemptions": eng.preempt_count,
            "poisoned": eng.poisoned_count,
            "queue_peak": eng.queue_peak,
            "latency_p50_s": pct(0.50), "latency_p99_s": pct(0.99),
            "tokens": toks, "secs": dt, "tok_per_sec": toks / dt,
            "ticks": eng.decode_calls, "deadlocked": deadlocked,
            "attn_mode": attn_mode, "kv_bits": kv_bits}


def bench_crash(params, cfg, policy, *, slots: int, requests: int,
                max_new: int, lengths, matmul_mode: str = "auto",
                attn_mode: str = "auto", kv_bits=None,
                attn_chunk: int = 1024, snapshot_every: int = 8,
                max_ticks: int = 4096) -> dict:
    """Kill-and-recover scenario: run with periodic snapshots + a
    write-ahead journal, inject a process kill mid-run, then recover a
    FRESH engine (restore latest snapshot, replay the journal tail) and
    finish the workload. Reports restore/replay cost (``restore_secs``,
    ``replayed_events``, ``resubmitted``) and verifies ZERO accepted-token
    loss: the union of pre-crash drains and the recovered run must be
    token-identical to an uncrashed reference at T=0 (``lost_requests``
    and ``mismatched_requests`` must both be 0 — the --check gate)."""
    import os
    import shutil
    import tempfile

    from repro.serving.resilience import FaultPlan, InjectedCrash

    def mk(**kw):
        return ServingEngine(params, cfg, policy=policy, slots=slots,
                             max_len=max(lengths) + max_new + 1,
                             dtype=jnp.float32, matmul_mode=matmul_mode,
                             attn_mode=attn_mode, kv_bits=kv_bits,
                             attn_chunk=attn_chunk, **kw)

    prompts = _prompts(requests, lengths)
    ref_eng = mk()
    for p in prompts:
        ref_eng.submit(p, max_new=max_new)
    ref = {r.uid: tuple(r.out) for r in ref_eng.run_all(max_ticks=max_ticks)}

    tmp = tempfile.mkdtemp(prefix="crashbench_")
    snaps, jpath = os.path.join(tmp, "snaps"), os.path.join(tmp, "wal.jsonl")
    # kill roughly mid-workload: past at least one periodic snapshot, well
    # before the last request finishes
    crash_tick = max(snapshot_every + 1, (requests * max_new) // (2 * slots))
    eng = mk(snapshot_dir=snaps, snapshot_every=snapshot_every,
             journal=jpath, fault_plan=FaultPlan(crash_at_tick=crash_tick))
    for p in prompts:
        eng.submit(p, max_new=max_new)
    delivered: dict = {}
    t0 = time.perf_counter()
    ticks = 0
    try:
        while eng.queue or eng._occupied():
            eng.step()
            ticks += 1
            delivered.update({r.uid: tuple(r.out) for r in eng.drain()})
            if ticks > max_ticks:
                break
    except InjectedCrash:
        pass
    uptime = time.perf_counter() - t0

    t1 = time.perf_counter()
    fresh = mk(snapshot_dir=snaps, journal=jpath)
    stats = fresh.recover()
    restore_secs = time.perf_counter() - t1
    t2 = time.perf_counter()
    recovered = {r.uid: tuple(r.out)
                 for r in fresh.run_all(max_ticks=max_ticks)}
    finish_secs = time.perf_counter() - t2
    shutil.rmtree(tmp, ignore_errors=True)

    merged = {**delivered, **recovered}
    lost = [u for u in ref if u not in merged]
    mismatched = [u for u in ref if u in merged and merged[u] != ref[u]]
    toks = sum(len(v) for v in recovered.values())
    return {"slots": slots, "requests": requests,
            "crash_tick": crash_tick, "uptime_secs": uptime,
            "snapshot_every": snapshot_every,
            "restored_step": stats["restored_step"],
            "replayed_events": stats["replayed_events"],
            "resubmitted": stats["resubmitted"],
            "restore_secs": restore_secs, "finish_secs": finish_secs,
            "recovered_tokens": toks,
            "delivered_pre_crash": len(delivered),
            "lost_requests": len(lost),
            "mismatched_requests": len(mismatched),
            "zero_loss": not lost and not mismatched,
            "attn_mode": attn_mode, "kv_bits": kv_bits}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--mix", default="mixed", choices=sorted(MIXES),
                    help="request traffic: 'mixed' short prompts over the "
                         "small admission buckets, 'long' 1k-4k prompts "
                         "where prefill attention dominates admission, "
                         "'overload' 2x-capacity arrivals under bounded "
                         "admission, 'crash' kill-and-recover durability")
    ap.add_argument("--slots", default=None,
                    help="comma-separated slot counts to sweep "
                         "(default per mix: mixed=1,4,8,16 long=1,2)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--forms", default="qp,q,w")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repetitions per config; best run reported "
                         "(default per mix: mixed=3 long=1)")
    ap.add_argument("--attn-chunk", type=int, default=None,
                    help="ref-mode chunked-prefill query-chunk length "
                         "(bounds the einsum score working set; default "
                         "per mix: mixed=1024 long=256)")
    ap.add_argument("--matmul-mode", default="auto",
                    choices=["auto", "kernel", "dequant"],
                    help="quantized-matmul dispatch for the q/qp forms "
                         "(kernel = Pallas, interpret mode off-TPU — slow "
                         "on CPU, for kernel-path measurement only)")
    ap.add_argument("--attn-mode", default="auto",
                    choices=["auto", "kernel", "ref"],
                    help="decode-attention dispatch (kernel = fused Pallas "
                         "attn_decode, interpret mode off-TPU)")
    ap.add_argument("--kv8", action="store_true",
                    help="serve from an int8 KV cache: halves the "
                         "cache-bytes-per-slot column")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding axis: a packed-3-bit drafter "
                         "(api.draft_of of the same checkpoint) proposes K "
                         "tokens per tick; adds the acc/tick column (tokens "
                         "committed per slot-tick, 1.0 without spec)")
    ap.add_argument("--draft-depth", type=float, default=1.0,
                    help="drafter depth fraction for --spec-k (0.5 = the "
                         "half-depth draft variant)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless qp tokens/sec is monotonically "
                         "increasing from 1 to 8 slots")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="JSON artifact path ('' disables)")
    args = ap.parse_args()

    lengths = MIXES[args.mix]
    d_slots, d_requests, d_max_new, d_repeats, d_chunk = MIX_DEFAULTS[args.mix]
    if args.slots is None:
        args.slots = d_slots
    if args.requests is None:
        args.requests = d_requests
    if args.max_new is None:
        args.max_new = d_max_new
    if args.repeats is None:
        args.repeats = d_repeats
    if args.attn_chunk is None:
        args.attn_chunk = d_chunk

    cfg = reduced(get_config(args.arch), layers=args.layers,
                  d_model=args.d_model, vocab=args.vocab)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    W3 = dataclasses.replace(W3A8, act_bits=None)
    form_params = {
        "w": (params, FLOAT),
        "q": (quant_dense.export_levels(params, W3), W3),
        "qp": (quant_dense.export_container(params, W3), W3),
    }
    # the drafter comes from the SAME checkpoint (self-speculation): every
    # form is verified by its own weights with the qp slice drafting
    draft = None
    if args.spec_k:
        from repro.models import api as model_api
        draft = model_api.draft_of(cfg, params, policy=W3,
                                   depth_fraction=args.draft_depth)
    slot_counts = [int(s) for s in args.slots.split(",")]

    results: dict = {}
    print(f"{cfg.name} reduced(L={args.layers}, d={args.d_model}, "
          f"V={args.vocab}), {args.requests} {args.mix}-mix requests "
          f"(prompt lens {lengths}) x {args.max_new} tokens")
    kv_bits = 8 if args.kv8 else None

    if args.mix == "overload":
        print(f"{'form':>4} {'slots':>5} {'subm':>5} {'acc':>4} "
              f"{'shed%':>6} {'dlmiss%':>7} {'preempt':>7} {'qpeak':>5} "
              f"{'p50_s':>7} {'p99_s':>7} {'tok/s':>8} {'wedged':>6}")
        for form in args.forms.split(","):
            p, pol = form_params[form]
            results[form] = []
            for slots in slot_counts:
                r = bench_overload(p, cfg, pol, slots=slots,
                                   requests=args.requests,
                                   max_new=args.max_new, lengths=lengths,
                                   matmul_mode=args.matmul_mode,
                                   attn_mode=args.attn_mode, kv_bits=kv_bits,
                                   attn_chunk=args.attn_chunk)
                results[form].append(r)
                print(f"{form:>4} {r['slots']:>5} {r['submitted']:>5} "
                      f"{r['accepted']:>4} {100 * r['shed_rate']:>6.1f} "
                      f"{100 * r['deadline_miss_rate']:>7.1f} "
                      f"{r['preemptions']:>7} {r['queue_peak']:>5} "
                      f"{r['latency_p50_s']:>7.3f} {r['latency_p99_s']:>7.3f} "
                      f"{r['tok_per_sec']:>8.1f} "
                      f"{str(r['deadlocked']):>6}")
        if args.out:
            artifact = {
                "bench": "serving", "arch": cfg.name,
                "reduced": {"layers": args.layers, "d_model": args.d_model,
                            "vocab": args.vocab},
                "requests": args.requests, "max_new": args.max_new,
                "mix": args.mix, "mix_lengths": lengths,
                "matmul_mode": args.matmul_mode,
                "attn_mode": args.attn_mode, "kv_bits": kv_bits,
                "results": results,
            }
            with open(args.out, "w") as f:
                json.dump(artifact, f, indent=2)
            print(f"wrote {args.out}")
        cells = [r for rs in results.values() for r in rs]
        # overload gate: the engine must never deadlock (every run drains
        # to completion under the watchdog) and bounded admission must not
        # degenerate into shedding EVERYTHING (some work always completes)
        ok = (bool(cells)
              and all(not r["deadlocked"] for r in cells)
              and all(r["shed_rate"] < 1.0 for r in cells)
              and all(r["completed_ok"] > 0 for r in cells))
        print(f"overload gate (no deadlock, shed-rate < 1.0, some requests "
              f"complete) over {len(cells)} cells: {ok}")
        if args.check and not ok:
            raise SystemExit(1)
        return

    if args.mix == "crash":
        out = args.out
        if out == "BENCH_serving.json":          # mix-specific default
            out = "BENCH_serving_durability.json"
        print(f"{'form':>4} {'slots':>5} {'ctick':>5} {'snap':>5} "
              f"{'replay':>6} {'resub':>5} {'restore_s':>9} {'finish_s':>8} "
              f"{'lost':>4} {'mism':>4} {'0loss':>5}")
        for form in args.forms.split(","):
            p, pol = form_params[form]
            results[form] = []
            for slots in slot_counts:
                r = bench_crash(p, cfg, pol, slots=slots,
                                requests=args.requests,
                                max_new=args.max_new, lengths=lengths,
                                matmul_mode=args.matmul_mode,
                                attn_mode=args.attn_mode, kv_bits=kv_bits,
                                attn_chunk=args.attn_chunk)
                results[form].append(r)
                print(f"{form:>4} {r['slots']:>5} {r['crash_tick']:>5} "
                      f"{str(r['restored_step']):>5} "
                      f"{r['replayed_events']:>6} {r['resubmitted']:>5} "
                      f"{r['restore_secs']:>9.3f} {r['finish_secs']:>8.1f} "
                      f"{r['lost_requests']:>4} {r['mismatched_requests']:>4} "
                      f"{str(r['zero_loss']):>5}")
        if out:
            artifact = {
                "bench": "serving_durability", "arch": cfg.name,
                "reduced": {"layers": args.layers, "d_model": args.d_model,
                            "vocab": args.vocab},
                "requests": args.requests, "max_new": args.max_new,
                "mix": args.mix, "mix_lengths": lengths,
                "matmul_mode": args.matmul_mode,
                "attn_mode": args.attn_mode, "kv_bits": kv_bits,
                "results": results,
            }
            with open(out, "w") as f:
                json.dump(artifact, f, indent=2)
            print(f"wrote {out}")
        cells = [r for rs in results.values() for r in rs]
        # durability gate: every cell must recover with ZERO accepted-token
        # loss (recovered+pre-crash drains token-identical to an uncrashed
        # run at T=0) AND must actually have restored from a snapshot
        ok = (bool(cells)
              and all(r["zero_loss"] for r in cells)
              and all(r["restored_step"] is not None for r in cells))
        print(f"durability gate (zero accepted-token loss, snapshot "
              f"restored) over {len(cells)} cells: {ok}")
        if args.check and not ok:
            raise SystemExit(1)
        return

    print(f"{'form':>4} {'slots':>5} {'tokens':>7} {'ticks':>6} "
          f"{'prefills':>8} {'secs':>7} "
          f"{'tok/s':>8} {'ptok/s':>8} {'acc/tick':>8} {'KB/slot':>8}")
    for form in args.forms.split(","):
        p, pol = form_params[form]
        results[form] = []
        for slots in slot_counts:
            r = bench_form(p, cfg, pol, slots=slots, requests=args.requests,
                           max_new=args.max_new, lengths=lengths,
                           repeats=args.repeats,
                           matmul_mode=args.matmul_mode,
                           attn_mode=args.attn_mode, kv_bits=kv_bits,
                           spec_k=args.spec_k, draft=draft,
                           attn_chunk=args.attn_chunk)
            results[form].append(r)
            print(f"{form:>4} {r['slots']:>5} {r['tokens']:>7} "
                  f"{r['ticks']:>6} {r['prefills']:>8} {r['secs']:>7.2f} "
                  f"{r['tok_per_sec']:>8.1f} {r['prompt_tok_per_sec']:>8.1f} "
                  f"{r['accepted_tok_per_tick']:>8.2f} "
                  f"{r['cache_bytes_per_slot'] / 1024:>8.1f}")

    if args.out:
        artifact = {
            "bench": "serving", "arch": cfg.name,
            "reduced": {"layers": args.layers, "d_model": args.d_model,
                        "vocab": args.vocab},
            "requests": args.requests, "max_new": args.max_new,
            "mix": args.mix, "mix_lengths": lengths,
            "repeats": args.repeats, "attn_chunk": args.attn_chunk,
            "matmul_mode": args.matmul_mode,
            "attn_mode": args.attn_mode, "kv_bits": kv_bits,
            "spec_k": args.spec_k, "draft_depth": args.draft_depth,
            "results": results,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out}")

    if args.spec_k:
        # speculative gate: every swept cell must commit MORE than one
        # token per slot-tick — i.e. the drafter earns its keep (the
        # tokens-per-tick multiplier the subsystem exists for)
        cells = [(f, r["slots"], r["accepted_tok_per_tick"])
                 for f, rs in results.items() for r in rs]
        ok = all(a > 1.0 for _, _, a in cells)
        print(f"spec_k={args.spec_k} accepted-tokens-per-tick > 1 in all "
              f"{len(cells)} cells: {ok} "
              f"(min {min(a for _, _, a in cells):.2f})")
        if args.check and not (cells and ok):
            raise SystemExit(1)
        return

    pts = [(r["slots"], r["tok_per_sec"]) for r in results.get("qp", ())
           if r["slots"] in (1, 4, 8)]
    ok = all(a[1] < b[1] for a, b in zip(pts, pts[1:]))
    if pts:
        print(f"qp amortization monotonic over slots "
              f"{'/'.join(str(s) for s, _ in pts)}: {ok} "
              f"({' -> '.join(f'{x:.1f}' for _, x in pts)} tok/s)")
    if args.check:
        # the gate must never pass vacuously: it needs the full qp 1/4/8 curve
        if {s for s, _ in pts} != {1, 4, 8}:
            raise SystemExit("--check needs form qp and slots 1,4,8 in the "
                             "sweep (got qp points for "
                             f"{sorted(s for s, _ in pts)})")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
