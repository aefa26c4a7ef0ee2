"""Ahead-of-time compiles of the serve kernels for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes whose trailing dims are not TPU tiles, operand layouts that
differ from XLA's, VMEM overruns. Each case here lowers one kernel at the
qwen2-1.5b serving widths (d_model 1536, d_ff 8960, vocab 151936, 12 query
heads over 2 KV heads of 128, 4 slots, an 80-position cache; the packed
matmuls at the benchmark's 16 slots and 16 x 64 prefill rows) through the
TPU compiler against a described — not attached — v5e chip, and checks the
compiled program calls the kernel (``tpu_custom_call``) under the kernel's
own name, which the benchmark's trace reader keys on. The attention
kernels read the stacked (L, B, S, KV*D) cache as stored, by layer index.

One whole program is compiled too: the decode tick (``decode_step``) at
those widths with 2 layers, 16 slots and 1280 positions, kernels on and the
cache donated, whose optimised HLO may hold no op that copies, reshapes,
slices or rewrites a layer's cache or more — only the in-place row writes.

The topology is described inside a fixture (never at import): only one
process may load the TPU library at a time, and a test worker that cannot
describe it skips these cases instead of failing collection.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attn_decode.kernel import attn_decode_pallas
from repro.kernels.attn_prefill.kernel import attn_prefill_pallas
from repro.kernels.qmatmul.kernel import qmatmul_pallas
from repro.kernels.qmatmul.ops import pick_blocks
from repro.kernels.qmatvec.kernel import (FIELDS, qmatvec_blocks,
                                          qmatvec_pallas)

D, FF, VOCAB = 1536, 8960, 151936
KV, G, HD = 2, 6, 128                  # 12 query heads = 2 KV heads x 6
SLOTS, CACHE = 4, 80                   # the serve CLI: 4 slots, 64 + 16 pos
QP_SLOTS, QP_BUCKET = 16, 64           # the benchmark's slots and prompts
# the seven packed matmuls of one qwen2-1.5b layer, (K, N)
QP_MATS = {"q": (D, D), "k": (D, KV * HD), "v": (D, KV * HD), "o": (D, D),
           "gate": (D, FF), "up": (D, FF), "down": (FF, D)}
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a chip compile cannot be read back without the chip: keep such
        # entries out of any persistent cache for the module's duration
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield t
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qmatvec(m, k, n):
    return (lambda x, w, d, b: qmatvec_pallas(x, w, d, b),
            [((m, k), BF16), ((-(-k // FIELDS), n), I32), ((n,), F32),
             ((n,), F32)])


def _qmatmul(m, k, n):
    bm, bn, bk = pick_blocks(m, n, k)
    return (lambda x, w, d: qmatmul_pallas(x, w, d, bm=bm, bn=bn, bk=bk),
            [((m, k), BF16), ((k, n), I8), ((n,), F32)])


def _attn_decode(kv_dtype, layers=2):
    """One layer, picked by a traced index, of the stacked stored cache."""
    cache = (layers, SLOTS, CACHE, KV * HD)
    shapes = [((SLOTS, KV, G, HD), BF16), (cache, kv_dtype),
              (cache, kv_dtype), ((SLOTS,), I32), ((), I32)]
    if kv_dtype == I8:
        shapes += [((layers, SLOTS, CACHE), F32)] * 2
    return (lambda *a: attn_decode_pallas(*a), shapes)


def _attn_prefill(t, s, kv_dtype, layers):
    """A prompt's own K/V (one layer) or the stacked decode cache (verify)."""
    cache = (layers, SLOTS, s, KV * HD)
    shapes = [((SLOTS, t, KV, G, HD), BF16), (cache, kv_dtype),
              (cache, kv_dtype), ((SLOTS, t), I32), ((SLOTS, t), I32),
              ((), I32)]
    if kv_dtype == I8:
        shapes += [((layers, SLOTS, s), F32)] * 2
    return (lambda *a: attn_prefill_pallas(*a), shapes)


CASES = {
    # bucketed prefill (M = slots x bucket) through the packed-container
    # qp kernel, the widest K; batched decode at the CLI's 4 slots, and at
    # the benchmark's 16 below, each matrix
    "qmatvec_prefill_down": lambda: _qmatvec(QP_SLOTS * QP_BUCKET, FF, D),
    "qmatvec_decode_up_cli": lambda: _qmatvec(SLOTS, D, FF),
    # levels-form kernel: an MLP projection and the tied 8-bit readout
    "qmatmul_up": lambda: _qmatmul(SLOTS, D, FF),
    "qmatmul_readout": lambda: _qmatmul(SLOTS, D, VOCAB),
    "attn_decode_bf16": lambda: _attn_decode(BF16),
    "attn_decode_int8": lambda: _attn_decode(I8),
    # bucketed-prefill admission (T = S = bucket) and speculative verify
    # (T = spec_k + 1 rows against the int8 decode cache)
    "attn_prefill_bucket": lambda: _attn_prefill(16, 16, BF16, 1),
    "attn_prefill_verify_int8": lambda: _attn_prefill(5, CACHE, I8, 2),
}
CASES.update({f"qmatvec_decode_{name}":
              (lambda k=k, n=n: _qmatvec(QP_SLOTS, k, n))
              for name, (k, n) in QP_MATS.items()})


# the instruction name each kernel's custom call carries: a chip trace's
# device ops are found by it (bench/devtrace.py reduces `<kernel>_pallas`
# as the kernel `<kernel>`)
KERNEL_CASES = {"qmatvec_pallas": "qmatvec_decode_up",
                "qmatmul_pallas": "qmatmul_up",
                "attn_decode_pallas": "attn_decode_bf16",
                "attn_prefill_pallas": "attn_prefill_bucket"}
_compiled = {}


def _compile(case, one_chip):
    if case not in _compiled:
        fn, shapes = CASES[case]()
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        _compiled[case] = jax.jit(fn).lower(*args).compile()
    return _compiled[case]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    compiled = _compile(case, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 << 30


@pytest.mark.parametrize("kernel", sorted(KERNEL_CASES))
def test_custom_call_carries_kernel_name(kernel, one_chip):
    text = _compile(KERNEL_CASES[kernel], one_chip).as_text()
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert any(ln.split(" = ", 1)[0].split()[-1].startswith(f"%{kernel}.")
               for ln in calls), calls


@pytest.mark.parametrize("m", [1, QP_SLOTS, QP_SLOTS * QP_BUCKET])
@pytest.mark.parametrize("name", sorted(QP_MATS))
def test_qmatvec_blocks_cover_kp(name, m):
    """One K block where KP is small (K 1536: KP 154 in one 160-word block,
    not two of 128 over a 256-word pad), a divisor of KP otherwise (K 8960:
    KP 896); N blocks divide N; decode rows ride in one M block."""
    k, n = QP_MATS[name]
    kp = -(-k // FIELDS)
    bm, bn, bkp = qmatvec_blocks(m, k, n)
    if k == D:
        assert (kp, bkp) == (154, 160)
    else:
        assert kp == 896 and kp % bkp == 0 and bkp % 128 == 0
    assert n % bn == 0 and bn % 128 == 0
    assert bm == min(m, 256) and m % bm == 0


# --- the decode tick: the stacked cache is written in place -------------------

TICK_LAYERS, TICK_SLOTS, TICK_CACHE = 2, 16, 1280     # decode_open's cache
# ops that pass a buffer through without moving its bytes
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "while", "opt-barrier", "call", "conditional"}
_HLO_OP = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(")


@pytest.fixture
def kernels_on(monkeypatch):
    """The kernels ask ``on_tpu`` whether to run in interpret mode; a
    described chip is not the default backend, so say yes here."""
    from repro.kernels.attn_prefill import ops as attn_prefill_ops
    from repro.kernels.qmatmul import ops as qmatmul_ops
    from repro.kernels.qmatvec import ops as qmatvec_ops
    for mod in (qmatmul_ops, qmatvec_ops, attn_prefill_ops):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def _whole_cache_ops(text, b, s, layer_elems):
    """Ops of optimised HLO whose result spans the slots and positions of
    the cache with one layer's elements or more, and moves bytes: (name,
    opcode, op_name). Pass-through ops are left out."""
    out = []
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if not m or m.group(4) in _PASS_THROUGH:
            continue
        dims = [int(x) for x in m.group(3).split(",") if x]
        n = 1
        for x in dims:
            n *= x
        if n >= layer_elems and b in dims and s in dims:
            meta = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(4), meta.group(1) if meta else ""))
    return out


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_decode_tick_writes_cache_in_place(kv_bits, one_chip, kernels_on):
    """The tick carries the stacked cache through its layer loop and writes
    only each layer's new rows: no copy, transpose, relayout, slice,
    dynamic-update-slice or scatter the size of a layer's cache or more,
    except the scatters of ``model.kv_write`` into the carried (donated)
    cache, which XLA does in place (it would insert a copy otherwise)."""
    from repro.configs import get_config
    from repro.core import quant_dense
    from repro.core.precision import W3A8
    from repro.models import api as model_api
    from repro.models import transformer

    cfg = dataclasses.replace(get_config("qwen2-1.5b"),
                              num_layers=TICK_LAYERS)
    params = jax.eval_shape(lambda: quant_dense.export_container(
        transformer.init(jax.random.PRNGKey(0), cfg), W3A8))
    cache = model_api.init_cache_abstract(cfg, TICK_SLOTS, TICK_CACHE,
                                          BF16, per_slot_len=True,
                                          kv_bits=kv_bits)
    assert cache["k"].shape == (TICK_LAYERS, TICK_SLOTS, TICK_CACHE,
                                KV * HD)

    def tick(p, c, tokens):
        return transformer.decode_step(p, c, tokens, cfg, policy=W3A8,
                                       dtype=BF16, matmul_mode="kernel",
                                       attn_mode="kernel")

    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((TICK_SLOTS, 1), I32, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    assert "attn_decode_pallas" in text and "qmatvec_pallas" in text
    big = _whole_cache_ops(text, TICK_SLOTS, TICK_CACHE,
                           TICK_SLOTS * TICK_CACHE * KV * HD)
    assert big, "the cache's in-place writes were not found"
    moved = [op for op in big if not op[2].endswith("model.kv_write/scatter")]
    assert not moved, moved
