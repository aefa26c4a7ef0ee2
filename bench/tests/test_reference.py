"""The plain reference against the program, at test size, in float32: the
same weights and quantization semantics must give the same logits through
bucketed prefill and cached decode, with the chip's kernel attention (in
interpret mode here)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import loop
from bench import weights as W
from bench.reference import qwen2 as R
from bench.families.qwen2 import program_config


def _program_logits(conf, params, prompt, n):
    """Greedy: prefill of the padded bucket, then n-1 cached decode steps,
    float32, kernel attention."""
    from repro.core.precision import W3A8
    from repro.models import transformer as T
    cfg = program_config(conf)
    p = len(prompt)
    bk = loop.bucket_of(p, 1280)
    toks = np.zeros((1, bk), np.int32)
    toks[0, :p] = prompt
    kw = dict(policy=W3A8, dtype=jnp.float32, matmul_mode="dequant",
              attn_mode="kernel")
    lg, cache = T.prefill(params, {"tokens": jnp.asarray(toks)}, cfg,
                          max_len=64, lengths=jnp.asarray([p]), **kw)
    out, logits = [int(jnp.argmax(lg[0, 0]))], [lg[0, 0]]
    for _ in range(n - 1):
        lg, cache = T.decode_step(params, cache, jnp.asarray([[out[-1]]]),
                                  cfg, **kw)
        logits.append(lg[0, 0])
        out.append(int(jnp.argmax(lg[0, 0])))
    return out, bk, jnp.stack(logits)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_program_logits(tiny_conf, tied):
    tiny_conf["model"]["tie_word_embeddings"] = tied
    s = W.Shapes.of(tiny_conf["model"])
    params = W.serve_params(5, s)
    prompt = np.random.default_rng(1).integers(1, s.vocab, 13).tolist()
    out, bk, prog = _program_logits(tiny_conf, params, prompt, 6)
    ref = R.Reference(tiny_conf["model"], 5).logits([(prompt, out, bk)])
    ref = ref[0, :len(out)]
    # float32 on both sides: what is left is summation order
    assert float(jnp.max(jnp.abs(prog - ref))) <= 1e-5 * float(
        jnp.max(jnp.abs(ref)))
    gaps = R.gaps(tiny_conf["model"], 5, [(prompt, out, bk)])
    assert gaps["served"] <= 1e-5 and gaps["tokens"] == len(out)


def test_pack3_is_the_programs_container():
    from repro.core import packing
    q = W.levels3(jax.random.PRNGKey(3), (37, 5))
    assert np.array_equal(np.asarray(W.pack3(q)),
                          np.asarray(packing.pack_matrix(q, 3)))
    assert int(q.min()) >= -3 and int(q.max()) <= 3


@pytest.mark.parametrize("tied", [True, False])
def test_serve_params_have_the_programs_layout(tiny_conf, tied):
    """Same tree, shapes and dtypes as the program's own export of its own
    initialisation."""
    from repro.core import quant_dense
    from repro.core.precision import W3A8
    from repro.models import get_model
    tiny_conf["model"]["tie_word_embeddings"] = tied
    cfg = program_config(tiny_conf)
    ours = jax.eval_shape(lambda: W.serve_params(1, W.Shapes.of(
        tiny_conf["model"])))
    theirs = jax.eval_shape(lambda: quant_dense.export_container(
        get_model(cfg).init(jax.random.PRNGKey(0), cfg), W3A8))
    def sig(t):
        return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert sig(ours) == sig(theirs)


def test_control_precisions_differ_from_reference(tiny_conf):
    reqs = [(list(range(1, 20)), [5, 6, 7, 8], 32)]
    ref = R.Reference(tiny_conf["model"], 2)
    h = ref.hidden(reqs)
    for c in ("fp8", "kv8"):
        assert float(jnp.max(jnp.abs(ref.hidden(reqs, c) - h))) > 0


def test_fixed_shape_pads_without_changing_gaps(tiny_conf):
    reqs = [(list(range(1, 20)), [5, 6, 7, 8], 32),
            (list(range(3, 12)), [9, 9], 16)]
    a = R.gaps(tiny_conf["model"], 4, reqs)
    b = R.gaps(tiny_conf["model"], 4, reqs, shape=(4, 64, 10))
    assert a["tokens"] == b["tokens"] == 6
    assert b["served"] == pytest.approx(a["served"], abs=1e-5)


def test_query_blocks_and_row_groups_change_no_number(tiny_conf):
    """Attention a few queries at a time, and the batch a few requests at a
    time, as the reference runs at 4k prompts, against all at once."""
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, 512, n).tolist(),
             rng.integers(1, 512, t).tolist(), bk)
            for n, t, bk in ((37, 9, 64), (5, 3, 8), (60, 12, 64))]
    ref = R.Reference(tiny_conf["model"], 6)
    whole = ref.hidden(reqs, rows=len(reqs), q_block=None)
    for rows, q_block in ((1, 16), (2, 7), (3, 200)):
        got = ref.hidden(reqs, rows=rows, q_block=q_block)
        assert got.shape == whole.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                                   rtol=0, atol=1e-5)
