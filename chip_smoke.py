#!/usr/bin/env python3
"""Bring-up smoke test: serve qwen2-1.5b at its published width on one TPU.

    python3 chip_smoke.py

One process, through the serving CLI's own code path (``repro.launch.serve``):
seeded f32 init of the full 28-layer model, export to the paper's packed
3-bit containers (``--quant w3 --form qp``), then 8 requests of the CLI's
mixed prompt lengths on 4 slots, 16 new tokens each, with the Pallas kernels
for every quantized matmul and every attention (``matmul_mode="kernel"``,
``attn_mode="kernel"``) and the degradation ladder off, so a kernel failure
fails the run instead of being rerouted to the reference paths.

Checks, each fatal:
  * the prefill logits of the kernel graphs match those of the reference
    graphs (``dequant`` matmuls, ``ref`` attention) on the same packed
    weights and the first admission bucket of the CLI's prompts, to within
    ``PARITY_TOL`` of the reference's largest logit, and both are finite;
  * every request finished ``ok`` with 16 tokens, no slot was quarantined
    for non-finite logits, and ``fallback_events`` is empty;
  * the compiled decode tick contains the kernels (``tpu_custom_call``).

Earlier lines report counts, host-clock times (compilation included), the
parity error and the compile-cache directory. The last line is one JSON
object naming the device; it is printed only when every check passed. With
no TPU, or outside a checkout of the repository, the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
# kernel-vs-reference prefill logits: max |kernel - ref| <= PARITY_TOL *
# max |ref|, compared in float32 at "highest" matmul precision without the
# 8-bit activation fake-quant. In bf16, or with the per-row dynamic
# activation grid, a last-bit difference flips roundings that 28 layers
# amplify to percents, which would hide a real kernel fault.
PARITY_TOL = 1e-3
SERVE_ARGS = ["--arch", "qwen2-1.5b", "--quant", "w3", "--form", "qp",
              "--matmul-mode", "kernel", "--attn-mode", "kernel",
              "--requests", "8", "--slots", "4", "--max-new", "16"]


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def prefill_parity(eng, prompts) -> dict:
    """Last-token prefill logits of ``prompts`` as one admission bucket
    (right-padded, per-row lengths), kernel graphs against reference
    graphs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import api as model_api

    policy = dataclasses.replace(eng.policy, act_bits=None)

    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((len(prompts), int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    def logits(matmul_mode, attn_mode):
        fn = jax.jit(lambda p, t, ln: model_api.prefill(
            p, {"tokens": t}, eng.cfg, policy=policy, dtype=jnp.float32,
            max_len=eng.max_len, lengths=ln, matmul_mode=matmul_mode,
            attn_mode=attn_mode)[0])
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(eng.params, jnp.asarray(toks),
                                 jnp.asarray(lens)))

    t0 = time.perf_counter()
    kern = logits("kernel", "kernel")
    ref = logits("dequant", "ref")
    err = float(np.max(np.abs(kern - ref)))
    scale = float(np.max(np.abs(ref)))
    return {"shape": list(kern.shape), "max_abs_err": err,
            "ref_max_abs": scale, "rel_err": err / max(scale, 1e-30),
            "finite": bool(np.isfinite(kern).all() and np.isfinite(ref).all()),
            "argmax_agree": float(np.mean(kern.argmax(-1) == ref.argmax(-1))),
            "secs": time.perf_counter() - t0}


def run(argv) -> None:
    """Serve once, print the report lines and check everything; raises
    AssertionError on a failed check."""
    import jax
    from repro.launch import serve

    args = serve.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    eng = serve.build_engine(args, degrade=False)
    jax.block_until_ready(eng.params)
    build_secs = time.perf_counter() - t0
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    print(f"built {eng.cfg.name} ({eng.cfg.num_layers} layers, d_model "
          f"{eng.cfg.d_model}, vocab {eng.cfg.vocab_size}) in "
          f"{build_secs:.1f}s: serve-form params {param_bytes / 1e9:.3f} GB",
          flush=True)

    prompts = serve.mixed_prompts(args.requests)
    par = prefill_parity(eng, prompts[:eng.slots])
    print(f"prefill parity kernel vs dequant/ref: logits {par['shape']}, "
          f"max abs err {par['max_abs_err']:.6g} over max |ref| "
          f"{par['ref_max_abs']:.6g} = {par['rel_err']:.6g} "
          f"(tolerance {PARITY_TOL}), argmax agreement "
          f"{par['argmax_agree']:.3f}, {par['secs']:.1f}s", flush=True)
    assert par["finite"], "non-finite prefill logits"
    assert par["rel_err"] <= PARITY_TOL, (
        f"kernel prefill logits differ from the reference by "
        f"{par['rel_err']:.4g} > {PARITY_TOL}")

    t0 = time.perf_counter()
    for prompt in prompts:
        eng.submit(prompt, max_new=args.max_new)
    done = eng.run_all()
    serve_secs = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {serve_secs:.1f}s "
          f"(host clock, compilation included), {eng.decode_calls} decode "
          f"ticks, {eng.prefill_calls} prefill calls, statuses "
          f"{sorted({r.status for r in done})}, fallback_events "
          f"{eng.fallback_events}", flush=True)
    assert len(done) == args.requests, f"{len(done)} of {args.requests} done"
    for r in done:
        assert r.status == "ok", f"request {r.uid} ended {r.status!r}"
        assert len(r.out) == args.max_new, (
            f"request {r.uid} produced {len(r.out)} tokens")
    assert eng.poisoned_count == 0, "slots quarantined for non-finite logits"
    assert eng.fallback_events == [], f"fallbacks: {eng.fallback_events}"

    t0 = time.perf_counter()
    tick = next(p for p in eng.contract_points() if p["name"] == "decode_tick")
    hlo = jax.jit(tick["fn"], donate_argnums=tick["donate"]).lower(
        *tick["args"]).compile().as_text()
    kernels = hlo.count("tpu_custom_call")
    print(f"compiled decode tick: {kernels} tpu_custom_call sites "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    assert kernels > 0, "decode tick has no Pallas kernel"

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device peak bytes in use {stats['peak_bytes_in_use']}")


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return _fail(f"run from a checkout of the repository ({e})")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return _fail(f"no TPU: JAX found {devs[0].platform}")
    print(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
          f"jax {jax.__version__}, compile cache {enable_compile_cache()}",
          flush=True)
    try:
        run(SERVE_ARGS)
    except AssertionError as e:
        return _fail(str(e))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
