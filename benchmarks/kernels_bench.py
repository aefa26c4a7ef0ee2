"""Kernel micro-bench + interpret-mode regression gate for the serve-path
matmuls AND the fused decode-attention kernel.

Two matmul shape cases mirror the LM serve path exactly:

  decode    (B=slots, K) x (K, N)            — one engine tick
  prefill   (slots*bucket_len, K) x (K, N)   — one bucketed admission

and three implementations per case:

  matmul_f32      float weights (the GPU-like baseline)
  dequant.q/qp    the fused serve fallback (quant_dense.serve_apply,
                  mode='dequant'): levels matmul'd in the activation dtype,
                  delta applied to the output — what 'auto' runs off-TPU
  kernel.q/qp     the Pallas qmatmul (levels) / qmatvec (containers) kernels
                  in interpret mode — numerics-exact stand-in for the TPU
                  path; timed only with --smoke-size shapes (interpret is an
                  emulator, its timings are not meaningful)

The attention cases mirror the three attention serving paths:

  decode        one engine tick — B=slots rows at mixed valid lengths
                against a (B, S, KV, D) cache; the fused
                ``kernels.attn_decode`` kernel (interpret mode) is
                parity-checked against BOTH its pure-jnp oracle
                (``attn_decode/ref.py``) and the production einsum path
                (``models.attention.decode_attention``).
  prefill       one bucketed admission — T x T prompt self-attention at
                T in {128, 512, 2048} (smoke: 24) with mixed per-row
                prompt lengths; the blocked online-softmax
                ``kernels.attn_prefill`` kernel is parity-checked against
                its einsum oracle (``attn_prefill/ref.py``) for bf16-class
                AND int8 KV, and each row's derived field quantifies the
                fp32 score-tensor bytes the einsum materializes in HBM vs
                the one VMEM tile the kernel holds.
  verify        one speculative tick — T = spec_k+1 in {3, 5} query rows
                against the live cache at mixed per-row frontiers; same
                kernel (T-row specialization), parity-checked against the
                oracle and the production guarded einsum
                (``models.attention.verify_attention``).

Every kernel case is PARITY-CHECKED; any mismatch exits nonzero, which is
the CI kernel-regression gate (`--smoke`). Each kernel case also carries a
``vmem_KB`` field — the static per-pallas_call on-chip working-set
estimate from ``repro.analysis`` (the same estimator the contract
linter's VMEM-budget pass gates on). Results are written to a JSON
artifact (default ``BENCH_kernels.json``) and archived next to
BENCH_serving.json.

    PYTHONPATH=src python benchmarks/kernels_bench.py           # timings
    PYTHONPATH=src python benchmarks/kernels_bench.py --smoke   # CI gate
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant_dense
from repro.core.packing import pack_matrix
from repro.core.precision import W3A8

# serve-path shapes: slots=8 decode tick, 8 slots x 16-token bucket prefill
FULL_CASES = [("decode", 8, 1024, 1024), ("prefill", 8 * 16, 1024, 1024)]
SMOKE_CASES = [("decode", 8, 96, 128), ("prefill", 8 * 16, 96, 128)]

# attn_decode shapes: (B=slots, S cache, H heads, KV heads, D head_dim)
ATTN_FULL = (8, 512, 8, 2, 64)
ATTN_SMOKE = (8, 96, 8, 2, 16)

# attn_prefill shapes: (B, T) bucketed-admission self-attention (S = T) and
# (B, T, S) speculative verify (T = spec_k+1 rows against the live cache);
# heads (H, KV, D) shared
PREFILL_FULL = [(4, 128), (4, 512), (1, 2048)]
PREFILL_SMOKE = [(2, 24)]
VERIFY_FULL = [(8, 3, 512), (8, 5, 512)]
VERIFY_SMOKE = [(4, 3, 48)]
PF_HEADS_FULL = (8, 2, 64)
PF_HEADS_SMOKE = (4, 2, 16)


def _vmem_kb(fn, *args):
    """Static on-chip working-set estimate for every pallas_call in the
    traced graph (repro.analysis: double-buffered block tiles + scratch,
    read off the BlockSpecs/grid — nothing is executed). Returns the
    LARGEST single kernel's estimate in KiB: kernels run one at a time,
    so the max is what must fit VMEM."""
    from repro.analysis.jaxpr_utils import find_pallas_eqns
    from repro.analysis.vmem import pallas_vmem_estimate

    jx = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    ests = [pallas_vmem_estimate(e)["vmem_bytes"]
            for e in find_pallas_eqns(jx)]
    return max(ests, default=0) / 2 ** 10


def _time(fn, *args, reps=10):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def _leaves(key, k, n):
    kx, kw = jax.random.split(key)
    w = jax.random.normal(kw, (k, n))
    q = jax.random.randint(kw, (k, n), -3, 4, jnp.int8)
    d = jnp.abs(jax.random.normal(kx, (n,))) * 0.1 + 0.01
    b = jax.random.normal(kx, (n,)) * 0.1
    qp = pack_matrix(q, 3)
    delta = d.reshape(1, n)
    return {
        "w": w,
        "q": {"q": q, "delta": delta, "b": b},
        "qp": {"qp": qp, "delta": delta, "b": b},
    }


def _parity(case, form, leaf, x, out):
    """Kernel output vs the dequantized effective_weight oracle."""
    w = quant_dense.effective_weight(leaf, W3A8, "hidden", k=x.shape[-1])
    ref = x @ w.astype(x.dtype) + leaf["b"]
    err = float(jnp.max(jnp.abs(out - ref)))
    ok = bool(np.allclose(np.asarray(out), np.asarray(ref),
                          rtol=1e-4, atol=1e-4))
    return {"case": f"{case}.{form}", "max_abs_err": err, "ok": ok}


def _stored(x):
    """(B, S, KV, D) -> the cache's stored layout, one layer: (1, B, S, KV*D)."""
    return x.reshape(x.shape[0], x.shape[1], -1)[None]


def _kv8(x):
    """(B, S, KV, D) -> int8 (B, S, KV, D) and its per-token (B, S) scales."""
    from repro.models.transformer import _quantize_kv
    q, sc = _quantize_kv(x.reshape(x.shape[0], x.shape[1], -1))
    return q.reshape(x.shape), sc


def attn_cases(smoke: bool = False):
    """Fused decode-attention parity: kernel vs ref.py vs decode_attention,
    bf16-class (f32 on CPU) and int8 cache, mixed per-row valid lengths."""
    from repro.kernels.attn_decode.ops import attn_decode
    from repro.kernels.attn_decode.ref import attn_decode_ref
    from repro.models.attention import decode_attention

    b, s, h, kv, d = ATTN_SMOKE if smoke else ATTN_FULL
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (b, s, kv, d))
    vc = jax.random.normal(ks[2], (b, s, kv, d))
    lens = (jnp.arange(b) * (s // b) % s + 1).astype(jnp.int32)  # mixed rows
    kq, ksc = _kv8(kc)
    vq, vsc = _kv8(vc)

    rows, parity = [], []
    reps = 3 if smoke else 10
    shape = f"shape={b}x{s}x{h}x{kv}x{d}"
    # the oracle reads (B, S, KV, D); kernel and einsum the stored layout
    for name, oargs, args in (
            ("bf16", (q, kc, vc, lens, None, None),
             (q, _stored(kc), _stored(vc), lens, None, None)),
            ("int8", (q, kq, vq, lens, ksc, vsc),
             (q, _stored(kq), _stored(vq), lens, ksc[None], vsc[None]))):
        f_kn = jax.jit(lambda *a: attn_decode(*a, interpret=True))
        out = f_kn(*args)
        ref = attn_decode_ref(*oargs)
        ein = decode_attention(*args, mode="ref")
        vkb = _vmem_kb(f_kn, *args)
        for oracle, o in (("ref", ref), ("einsum", ein)):
            err = float(jnp.max(jnp.abs(out - o)))
            ok = bool(np.allclose(np.asarray(out), np.asarray(o),
                                  rtol=1e-4, atol=1e-4))
            parity.append({"case": f"attn_decode.{name}.vs_{oracle}",
                           "max_abs_err": err, "ok": ok,
                           "vmem_kb": round(vkb, 1)})
        f_ref = jax.jit(lambda *a: decode_attention(*a, mode="ref"))
        rows.append((f"kernel.cpu.attn_decode.{name}.einsum",
                     _time(f_ref, *args, reps=reps), shape))
        if smoke:
            rows.append((f"kernel.cpu.attn_decode.{name}.kernel.interpret",
                         _time(f_kn, *args, reps=reps),
                         f"{shape};vmem_KB={vkb:.1f}"))
    return rows, parity


def attn_prefill_cases(smoke: bool = False):
    """Blocked prefill/verify attention: kernel (interpret) vs its einsum
    oracle (attn_prefill/ref.py), bf16-class and int8 KV, mixed per-row
    lengths/frontiers; derived fields quantify the fp32 score bytes the
    einsum puts in HBM vs the single VMEM tile the kernel holds."""
    from repro.kernels.attn_prefill.ops import attn_prefill
    from repro.kernels.attn_prefill.ref import attn_prefill_ref
    from repro.models.attention import verify_attention

    h, kv, d = PF_HEADS_SMOKE if smoke else PF_HEADS_FULL
    g = h // kv
    reps = 3 if smoke else 10
    rows, parity = [], []

    def oracle(q, k, v, hi, ks_=None, vs_=None):
        b, t = q.shape[:2]
        qg = (q * (d ** -0.5)).reshape(b, t, kv, g, d)
        lo = jnp.zeros((b, t), jnp.int32)
        return attn_prefill_ref(qg, k, v, lo, hi, ks_,
                                vs_).reshape(q.shape)

    def one(tag, q, k, v, hi, ks_=None, vs_=None):
        """Parity-check one case; returns (kernel_fn, shape+derived str)."""
        b, t = q.shape[:2]
        s = k.shape[1]
        f_kn = jax.jit(lambda *a: attn_prefill(
            a[0], a[1], a[2], a[3], k_scale=a[4] if len(a) > 4 else None,
            v_scale=a[5] if len(a) > 5 else None, interpret=True))
        args = ((q, _stored(k), _stored(v), hi)
                + (() if ks_ is None else (ks_[None], vs_[None])))
        out = f_kn(*args)
        ref = oracle(q, k, v, hi, ks_, vs_)
        err = float(jnp.max(jnp.abs(out - ref)))
        ok = bool(np.allclose(np.asarray(out), np.asarray(ref),
                              rtol=1e-4, atol=1e-4))
        vkb = _vmem_kb(f_kn, *args)
        parity.append({"case": tag, "max_abs_err": err, "ok": ok,
                       "vmem_kb": round(vkb, 1)})
        ein_mb = b * kv * g * t * s * 4 / 2 ** 20     # (B,KV,G,T,S) fp32
        tile_kb = min(128, t) * g * min(128, s) * 4 / 2 ** 10
        shape = (f"shape={b}x{t}x{s}x{h}x{kv}x{d};"
                 f"score_einsum_MB={ein_mb:.2f};score_tile_KB={tile_kb:.1f};"
                 f"vmem_KB={vkb:.1f}")
        return f_kn, args, shape

    # bucketed admission: T x T self-attention, mixed per-row prompt lengths
    for b, t in (PREFILL_SMOKE if smoke else PREFILL_FULL):
        ks3 = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks3[0], (b, t, h, d))
        kc = jax.random.normal(ks3[1], (b, t, kv, d))
        vc = jax.random.normal(ks3[2], (b, t, kv, d))
        lens = jnp.maximum((jnp.arange(b) + 1) * t // b, 1).astype(jnp.int32)
        pos = jnp.arange(t, dtype=jnp.int32)
        hi = jnp.minimum(pos[None, :] + 1, lens[:, None])
        kq, ksc = _kv8(kc)
        vq, vsc = _kv8(vc)
        for name, args in (("bf16", (q, kc, vc, hi)),
                           ("int8", (q, kq, vq, hi, ksc, vsc))):
            f_kn, full_args, shape = one(f"attn_prefill.T{t}.{name}", *args)
            f_ein = jax.jit(lambda *a: oracle(*a))
            rows.append((f"kernel.cpu.attn_prefill.T{t}.{name}.einsum",
                         _time(f_ein, *args, reps=reps), shape))
            rows.append((f"kernel.cpu.attn_prefill.T{t}.{name}"
                         f".kernel.interpret",
                         _time(f_kn, *full_args, reps=reps), shape))

    # speculative verify: T = spec_k+1 rows against the live cache
    for b, t, s in (VERIFY_SMOKE if smoke else VERIFY_FULL):
        ks3 = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks3[0], (b, t, h, d))
        kc = jax.random.normal(ks3[1], (b, s, kv, d))
        vc = jax.random.normal(ks3[2], (b, s, kv, d))
        pos0 = ((jnp.arange(b) * (s // b)) % (s - t)).astype(jnp.int32)
        valid = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :] + 1
        kq, ksc = _kv8(kc)
        vq, vsc = _kv8(vc)
        for name, args in (("bf16", (q, kc, vc, valid)),
                           ("int8", (q, kq, vq, valid, ksc, vsc))):
            f_kn, full_args, shape = one(f"attn_verify.T{t}.{name}", *args)
            out = f_kn(*full_args)
            # also gate against the PRODUCTION guarded-einsum verify path
            ein = verify_attention(*full_args[:4], *(full_args[4:] or
                                                     (None, None)),
                                   mode="ref")
            err = float(jnp.max(jnp.abs(out - ein)))
            ok = bool(np.allclose(np.asarray(out), np.asarray(ein),
                                  rtol=1e-4, atol=1e-4))
            parity.append({"case": f"attn_verify.T{t}.{name}.vs_production",
                           "max_abs_err": err, "ok": ok})
            f_ein = jax.jit(lambda a0, a1, a2, a3, *sc: verify_attention(
                a0, a1, a2, a3, *sc, mode="ref"))
            rows.append((f"kernel.cpu.attn_verify.T{t}.{name}.einsum",
                         _time(f_ein, *full_args, reps=reps), shape))
            if smoke:
                rows.append((f"kernel.cpu.attn_verify.T{t}.{name}"
                             f".kernel.interpret",
                             _time(f_kn, *full_args, reps=reps), shape))
    return rows, parity


def run_cases(smoke: bool = False):
    rows, parity = [], []
    reps = 3 if smoke else 10
    for case, m, k, n in (SMOKE_CASES if smoke else FULL_CASES):
        leaves = _leaves(jax.random.PRNGKey(0), k, n)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
        shape = f"shape={m}x{k}x{n}"

        f_float = jax.jit(lambda x, w: x @ w)
        rows.append((f"kernel.cpu.{case}.matmul_f32",
                     _time(f_float, x, leaves["w"], reps=reps), shape))
        for form in ("q", "qp"):
            leaf = leaves[form]
            f_dq = jax.jit(lambda x, lf=leaf: quant_dense.serve_apply(
                lf, x, mode="dequant"))
            rows.append((f"kernel.cpu.{case}.dequant.{form}",
                         _time(f_dq, x, reps=reps), shape))
            # interpret-mode Pallas path: parity-checked always, timed only
            # at smoke sizes (the interpret emulator's speed is meaningless)
            f_kn = jax.jit(lambda x, lf=leaf: quant_dense.serve_apply(
                lf, x, mode="kernel", interpret=True))
            out = f_kn(x)
            p = _parity(case, form, leaf, x, out)
            p["vmem_kb"] = round(_vmem_kb(f_kn, x), 1)
            parity.append(p)
            if smoke:
                rows.append((f"kernel.cpu.{case}.kernel.{form}.interpret",
                             _time(f_kn, x, reps=reps),
                             f"{shape};vmem_KB={p['vmem_kb']}"))
    arows, aparity = attn_cases(smoke=smoke)
    prows, pparity = attn_prefill_cases(smoke=smoke)
    return rows + arows + prows, parity + aparity + pparity


def run(smoke: bool = True):
    """Harness entry (benchmarks/run.py): flat name,us,derived rows."""
    rows, parity = run_cases(smoke=smoke)
    return rows + [(f"kernel.parity.{p['case']}", 0.0,
                    f"max_abs_err={p['max_abs_err']:.2e};"
                    f"{'ok' if p['ok'] else 'FAIL'}") for p in parity]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + exit nonzero on any kernel-vs-"
                         "oracle parity failure (the CI gate)")
    ap.add_argument("--out", default="BENCH_kernels.json",
                    help="JSON artifact path ('' disables)")
    args = ap.parse_args()

    rows, parity = run_cases(smoke=args.smoke)
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    bad = [p for p in parity if not p["ok"]]
    for p in parity:
        print(f"parity.{p['case']},{p['max_abs_err']:.2e},"
              f"{'ok' if p['ok'] else 'FAIL'}")

    if args.out:
        artifact = {"bench": "kernels", "smoke": args.smoke,
                    "rows": [{"name": n, "us": us, "derived": d}
                             for n, us, d in rows],
                    "parity": parity}
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out}")

    if bad:
        raise SystemExit(f"kernel parity FAILED: {[p['case'] for p in bad]}")


if __name__ == "__main__":
    main()
