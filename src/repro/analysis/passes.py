"""The six contract passes. Each is a pure function over a closed jaxpr
(or, for the whole-function checks, an abstract-evaluable callable) and
returns a list of :class:`Violation` — empty means the contract holds.
Nothing here executes a graph: jaxprs come from ``jax.make_jaxpr``, avals
from ``jax.eval_shape``, donation from ``jax.jit(...).lower`` on
ShapeDtypeStructs.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.analysis.jaxpr_utils import (eqn_label, find_pallas_eqns,
                                        float_shapes_outside_pallas,
                                        iter_eqns)
from repro.analysis.vmem import DEFAULT_VMEM_BUDGET, pallas_vmem_estimate

__all__ = ["Violation", "check_no_dequant", "check_no_quadratic_scores",
           "check_no_host_callback", "check_scan_carries",
           "check_carry_fixed_point", "check_donation", "check_vmem_budget"]


@dataclasses.dataclass
class Violation:
    """One broken contract: which pass fired, an actionable message, and
    (when attributable) the offending equation."""
    check: str
    message: str
    eqn: str = ""

    def __str__(self) -> str:
        loc = f" [at: {self.eqn}]" if self.eqn else ""
        return f"{self.check}: {self.message}{loc}"

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


# --- pass 1: no dequantized weight tensor -----------------------------------------

def check_no_dequant(jaxpr, forbidden_shapes: Iterable[tuple], *,
                     require_pallas: bool = True) -> List[Violation]:
    """No float tensor of a quantized weight's (stacked or per-layer) shape
    may appear outside the Pallas kernels: a hit means the graph
    materialized a dequantized weight matrix in HBM — exactly what the
    3-bit serve forms exist to avoid. ``require_pallas`` additionally
    demands the graph actually lowered to pallas_call (kernel mode that
    silently fell back to a fallback path is itself a violation)."""
    shapes, saw = float_shapes_outside_pallas(jaxpr)
    forbidden = set(map(tuple, forbidden_shapes))
    out = [Violation("no_dequant",
                     f"float tensor of quantized-weight shape {sh} is "
                     f"materialized outside the Pallas kernels (dequantized "
                     f"weight in the serve graph)", eqn=shapes[sh])
           for sh in sorted(set(shapes) & forbidden)]
    if require_pallas and not saw:
        out.append(Violation("no_dequant",
                             "graph contains no pallas_call: kernel mode "
                             "did not lower to the Pallas kernels"))
    return out


# --- pass 2: no quadratic score tensor --------------------------------------------

def check_no_quadratic_scores(jaxpr, t: int, s: int, *, min_rank: int = 2,
                              require_pallas: bool = False) -> List[Violation]:
    """No float tensor whose trailing dims are (T, S) may appear outside
    the Pallas kernels in a kernel-mode prefill/verify graph: the blocked
    online-softmax kernel keeps the score tile in VMEM, so a full (..., T,
    S) float result means the quadratic HBM intermediate is back.
    ``min_rank`` filters accidental shape collisions at coarse contract
    points (real attention score tensors are (B, KV, G, T, S))."""
    shapes, saw = float_shapes_outside_pallas(jaxpr)
    out = [Violation("no_quadratic_scores",
                     f"float score tensor {sh} with trailing dims "
                     f"(T={t}, S={s}) materialized outside the Pallas "
                     f"kernels (quadratic HBM intermediate)", eqn=shapes[sh])
           for sh in sorted(shapes)
           if len(sh) >= max(2, min_rank) and tuple(sh[-2:]) == (t, s)]
    if require_pallas and not saw:
        out.append(Violation("no_quadratic_scores",
                             "graph contains no pallas_call: kernel mode "
                             "did not lower to the Pallas kernels"))
    return out


# --- pass 3: no host callback / transfer ------------------------------------------

# primitive names that sync with or transfer to the host: any callback
# flavor (pure_callback / io_callback / debug_callback) plus explicit
# placement/transfer ops. A jitted serving tick containing one of these
# cannot be async — it re-introduces the per-token host sync.
_TRANSFER_PRIMS = ("device_put", "infeed", "outfeed")
# callbacks traced under a primitive of their own: jax.debug.print is a
# debug_callback that appears in the jaxpr as ``debug_print``
_CALLBACK_ALIASES = {"debug_print": "debug_callback"}


def check_no_host_callback(jaxpr) -> List[Violation]:
    out = []
    for eqn in iter_eqns(jaxpr, descend_pallas=True):
        name = eqn.primitive.name
        kind = _CALLBACK_ALIASES.get(name, name)
        if not ("callback" in kind or kind in _TRANSFER_PRIMS):
            continue
        what = f"'{name}'" + (f" (a {kind})" if kind != name else "")
        out.append(Violation(
            "no_host_callback",
            f"host-sync primitive {what} inside a jitted serving "
            f"graph (breaks the async no-per-token-sync contract)",
            eqn=eqn_label(eqn)))
    return out


# --- pass 4: carry dtype drift ----------------------------------------------------

def _leaf_sig(x):
    return tuple(x.shape), jnp.dtype(x.dtype)


def check_carry_fixed_point(fn, args: Sequence, carry_map: Dict[int, int],
                            *, point: str = "") -> List[Violation]:
    """Abstract-eval ``fn(*args)`` and require every carried buffer to be
    an aval FIXED POINT: ``carry_map`` maps input argnum -> output index,
    and each mapped pair must agree leaf-for-leaf in shape and dtype.

    This is the static catcher for the PR 5 ``mamba2.block_decode`` bug
    class: a tick whose output cache drifts to a different dtype than its
    input cache silently retraces on every invocation (and breaks any
    scan/while carry built over it). Args may be concrete arrays or
    ShapeDtypeStructs — nothing is executed."""
    label = point or getattr(fn, "__name__", "fn")
    # a fresh wrapper object per call: jax caches abstract-eval traces
    # keyed on the function object, and a stale trace would hide drift
    # introduced after a previous clean check of the same fn
    out = jax.eval_shape(lambda *a: fn(*a), *args)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    viols: List[Violation] = []
    for argnum, outidx in sorted(carry_map.items()):
        fin, tin = jtu.tree_flatten_with_path(args[argnum])
        fout, tout = jtu.tree_flatten_with_path(out[outidx])
        if tin != tout:
            viols.append(Violation(
                "carry_dtype",
                f"{label}: carried arg {argnum} -> output {outidx} changed "
                f"pytree structure across the tick"))
            continue
        for (path, a), (_, b) in zip(fin, fout):
            if _leaf_sig(a) != _leaf_sig(b):
                viols.append(Violation(
                    "carry_dtype",
                    f"{label}: carried arg {argnum}{jtu.keystr(path)} is "
                    f"{jnp.dtype(a.dtype).name}{list(a.shape)} going in but "
                    f"{jnp.dtype(b.dtype).name}{list(b.shape)} coming out — "
                    f"not an aval fixed point, so every tick retraces "
                    f"(and a scan/while carry over it fails)"))
    return viols


def check_scan_carries(jaxpr) -> List[Violation]:
    """Defense-in-depth companion: every scan/while carry INSIDE the graph
    must keep fixed avals across iterations. JAX enforces this at trace
    time for its own control-flow primitives, so on today's jax a traced
    graph can't violate it — but custom primitives and future versions
    can, and the check documents the invariant where the report lives."""
    out = []
    for eqn in iter_eqns(jaxpr, descend_pallas=False):
        if eqn.primitive.name == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
            pairs = zip(inner.invars[nc:nc + ncarry], inner.outvars[:ncarry])
        elif eqn.primitive.name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            nc = eqn.params["body_nconsts"]
            pairs = zip(body.invars[nc:], body.outvars)
        else:
            continue
        for i, (a, b) in enumerate(pairs):
            aa, bb = getattr(a, "aval", None), getattr(b, "aval", None)
            if aa is None or bb is None:
                continue
            if (tuple(aa.shape), jnp.dtype(aa.dtype)) != \
                    (tuple(bb.shape), jnp.dtype(bb.dtype)):
                out.append(Violation(
                    "carry_dtype",
                    f"{eqn.primitive.name} carry {i} drifts "
                    f"{jnp.dtype(aa.dtype).name}{list(aa.shape)} -> "
                    f"{jnp.dtype(bb.dtype).name}{list(bb.shape)} across "
                    f"iterations", eqn=eqn_label(eqn)))
    return out


# --- pass 5: donation honored -----------------------------------------------------

def check_donation(fn, args: Sequence, donate_argnums: Sequence[int], *,
                   point: str = "") -> List[Violation]:
    """Lower a FRESH ``jax.jit(fn, donate_argnums=...)`` over the given
    (possibly abstract) args and require the donation to take: every
    donated input the lowering could not pair with an output of the same
    shape and dtype is a violation (the aliasing fallback path — the tick
    would silently copy the whole cache). The lowering leaves such inputs
    in the module as ``jax.buffer_donor``, or warns "donated buffers were
    not usable" where it drops them outright. At least one input must
    actually alias an output in the lowered module.
    Building a private jit keeps the check from polluting the caller's jit
    caches (trace-count budgets stay honest)."""
    label = point or getattr(fn, "__name__", "fn")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # fresh wrapper: same trace-cache-staleness defense as the carry
        # pass, and it guarantees this private jit shares no cache with
        # the caller's jitted fns (trace-count budgets stay honest)
        text = jax.jit(lambda *a: fn(*a),
                       donate_argnums=tuple(donate_argnums)) \
            .lower(*args).as_text()
    viols = []
    for w in caught:
        msg = str(w.message)
        if "donated" in msg.lower():
            viols.append(Violation(
                "donation",
                f"{label}: donation fell back to a copy — {msg[:300]}"))
    unpaired = text.count("jax.buffer_donor = true")
    if unpaired:
        viols.append(Violation(
            "donation",
            f"{label}: donation fell back to a copy — {unpaired} donated "
            f"input(s) alias no output of the same shape and dtype"))
    if "tf.aliasing_output" not in text:
        viols.append(Violation(
            "donation",
            f"{label}: no donated input aliases any output "
            f"(donate_argnums={tuple(donate_argnums)} had no effect; the "
            f"cache is copied every call)"))
    return viols


# --- pass 6: Pallas VMEM budget ---------------------------------------------------

def check_vmem_budget(jaxpr, budget_bytes: int = DEFAULT_VMEM_BUDGET,
                      ) -> List[Violation]:
    """Every pallas_call's estimated on-chip working set (double-buffered
    block tiles + scratch, from the BlockSpecs/grid — see
    :func:`repro.analysis.vmem.pallas_vmem_estimate`) must fit the VMEM
    budget. This is the paper's on-chip-memory contract in bytes."""
    out = []
    for eqn in find_pallas_eqns(jaxpr):
        est = pallas_vmem_estimate(eqn)
        if est["vmem_bytes"] > budget_bytes:
            big = sorted((r for r in est["refs"] if r[0] != "prefetch"),
                         key=lambda r: -r[3])[:3]
            detail = ", ".join(f"{k} {d}{list(sh)} = {b} B"
                               for k, sh, d, b in big)
            out.append(Violation(
                "vmem_budget",
                f"kernel '{est['name']}' (grid {est['grid']}) estimated "
                f"VMEM {est['vmem_bytes']} B exceeds budget "
                f"{budget_bytes} B; largest refs: {detail}",
                eqn=eqn_label(eqn)))
    return out
