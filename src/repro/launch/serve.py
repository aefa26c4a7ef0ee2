"""Serving launcher: quantize-and-serve any assigned arch through the batched
continuous-batching engine (one jitted decode per tick, all slots at once).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 8 --slots 4 --max-new 16

Speculative serving (the 3-bit drafter proposes, the serving form verifies):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --quant float --spec-k 4 --requests 8 --slots 4 --max-new 16

Overload-hardened serving (bounded admission + deadlines + preemption +
watchdog; prints the resilience counters after the run):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 16 --slots 2 --queue-limit 8 --shed-policy drop_oldest \
        --deadline 48 --preempt 8 --max-ticks 512

Durable serving (periodic snapshots + write-ahead journal + weight-store
integrity probe; ``--resume`` recovers a killed run from the latest
snapshot plus the journal tail before serving):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 8 --slots 4 --snapshot-dir /tmp/snaps --snapshot-every 16 \
        --journal /tmp/serve.jsonl --integrity-every 32 [--resume]
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, reduced
from repro.core import quant_dense
from repro.core.precision import FLOAT, W3A8
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serving.engine import ServingEngine

# mixed prompt lengths: exercises the length-bucketed batched admission
PROMPT_LENS = (4, 8, 5, 12, 3, 16, 7, 9)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="w3", choices=["float", "w3"])
    ap.add_argument("--form", default="qp", choices=["w", "q", "qp"],
                    help="weight form for --quant w3: levels (q) or packed "
                         "containers (qp, the paper's BRAM image)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--matmul-mode", default="auto",
                    choices=["auto", "kernel", "dequant"],
                    help="quantized-matmul dispatch: Pallas kernels, fused "
                         "dequant fallback, or auto (kernel on TPU)")
    ap.add_argument("--attn-mode", default="auto",
                    choices=["auto", "kernel", "ref"],
                    help="attention dispatch for prefill admission, "
                         "speculative verify AND per-token decode: Pallas "
                         "kernels (blocked prefill/verify + fused decode), "
                         "einsum/chunked reference, or auto (kernel on TPU)")
    ap.add_argument("--kv8", action="store_true",
                    help="serve from an int8 KV cache (per-token scales; "
                         "half the cache bytes per slot — attention "
                         "families only)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: the packed-3-bit drafter "
                         "(api.draft_of of the same checkpoint) proposes "
                         "K tokens per tick, the serving weights verify "
                         "them in one multi-token pass (dense/moe/hybrid; "
                         "ssm rejects)")
    ap.add_argument("--draft-depth", type=float, default=1.0,
                    help="fraction of the layer stack the drafter keeps "
                         "(1.0 = full-depth self-draft)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bounded admission: queued requests past this "
                         "depth are shed per --shed-policy")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "drop_oldest"],
                    help="what bounded admission sheds when the queue is "
                         "full: the new request, or the oldest queued one")
    ap.add_argument("--deadline", type=int, default=None,
                    help="default per-request deadline in decode ticks; "
                         "expired requests are cancelled mid-stream "
                         "(partial output, status='deadline')")
    ap.add_argument("--preempt", type=int, default=None,
                    help="preempt a slot held this many ticks when the "
                         "queue has waiters; the request requeues with its "
                         "committed tokens (token-exact at T=0)")
    ap.add_argument("--max-ticks", type=int, default=None,
                    help="watchdog: abort run_all with a diagnostic dump "
                         "after this many driver iterations")
    ap.add_argument("--snapshot-dir", default=None,
                    help="durability: persist atomic engine snapshots here "
                         "(device caches + host bookkeeping + RNG key)")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="snapshot every N decode ticks (needs "
                         "--snapshot-dir)")
    ap.add_argument("--journal", default=None,
                    help="write-ahead JSONL journal of submit/admit/commit/"
                         "finish/shed events (the replay tail for --resume)")
    ap.add_argument("--resume", action="store_true",
                    help="recover before serving: restore the latest "
                         "snapshot under --snapshot-dir and resubmit the "
                         "journal tail (then ALSO submit this run's "
                         "requests)")
    ap.add_argument("--integrity-every", type=int, default=None,
                    help="run the weight-store canary fingerprint probe "
                         "every N ticks; detected corruption is healed "
                         "from the golden copy")
    ap.add_argument("--golden-dir", default=None,
                    help="also persist the golden weight copy + CRC "
                         "manifest here (checkpoint.integrity)")
    return ap


def build_engine(args, **engine_kw) -> ServingEngine:
    """Seeded init, export to the serve form ``args`` asks for, and the
    engine. ``engine_kw`` overrides ServingEngine keywords (e.g. the
    degradation ladder)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    draft_cfg = draft_params = None
    if args.spec_k:
        # derive the drafter from the master float weights BEFORE the
        # serving form is exported (draft_of re-exports its slice to qp)
        from repro.models import api as model_api
        draft_cfg, draft_params = model_api.draft_of(
            cfg, params, depth_fraction=args.draft_depth)
    if args.quant == "w3":
        export = {"q": quant_dense.export_levels,
                  "qp": quant_dense.export_container}.get(args.form)
        if export:
            params = export(params, W3A8)
        policy = W3A8
    else:
        policy = FLOAT

    kw = dict(slots=args.slots, max_len=64 + args.max_new + args.spec_k,
              temperature=args.temperature, eos_id=args.eos_id,
              matmul_mode=args.matmul_mode, attn_mode=args.attn_mode,
              kv_bits=8 if args.kv8 else None,
              spec_k=args.spec_k, draft_params=draft_params,
              draft_cfg=draft_cfg,
              queue_limit=args.queue_limit, shed_policy=args.shed_policy,
              default_deadline=args.deadline, preempt_after=args.preempt,
              max_ticks=args.max_ticks,
              snapshot_dir=args.snapshot_dir,
              snapshot_every=args.snapshot_every, journal=args.journal,
              integrity_every=args.integrity_every,
              golden_dir=args.golden_dir)
    kw.update(engine_kw)
    return ServingEngine(params, cfg, policy=policy, **kw)


def mixed_prompts(requests: int) -> list:
    """The CLI's ``requests`` prompts, cycling through ``PROMPT_LENS``."""
    return [[(1 + i + j) % 50 + 1
             for j in range(PROMPT_LENS[i % len(PROMPT_LENS)])]
            for i in range(requests)]


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    eng = build_engine(args)
    if args.resume:
        stats = eng.recover()
        print(f"recovered: snapshot step {stats['restored_step']}, "
              f"{stats['replayed_events']} journal events replayed, "
              f"{stats['resubmitted']} requests resubmitted")
    t0 = time.time()
    for prompt in mixed_prompts(args.requests):
        eng.submit(prompt, max_new=args.max_new)
    done = eng.run_all()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    spec = (f", spec accept rate {eng.spec_accept_rate:.2f} "
            f"(K={args.spec_k})" if args.spec_k else "")
    print(f"{len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s on {jax.devices()[0].device_kind}, "
          f"compile included), "
          f"{eng.decode_calls} batched decode ticks "
          f"({toks / max(eng.decode_calls, 1):.2f} tok/tick), "
          f"{eng.prefill_calls} bucketed prefill calls "
          f"({len(done) / max(eng.prefill_calls, 1):.2f} req/prefill)"
          f"{spec}")
    if eng.fallback_events:
        print(f"fallback_events (tick, ladder step): {eng.fallback_events}")
    if (args.queue_limit is not None or args.deadline is not None
            or args.preempt is not None):
        by_status: dict = {}
        for r in done:
            by_status[r.status] = by_status.get(r.status, 0) + 1
        print(f"resilience: statuses {by_status}, "
              f"shed {eng.shed_count}, "
              f"deadline misses {eng.deadline_miss_count}, "
              f"preemptions {eng.preempt_count}, "
              f"poisoned {eng.poisoned_count}, "
              f"queue peak {eng.queue_peak}")
    if (args.snapshot_dir is not None or args.journal is not None
            or args.integrity_every is not None):
        print(f"durability: snapshots written {eng.snapshots_written}, "
              f"journal events {eng.journal_events}, "
              f"replayed {eng.replayed_events}, "
              f"integrity probes {eng.integrity_probes}, "
              f"heals {eng.heal_count}")


if __name__ == "__main__":
    main()
