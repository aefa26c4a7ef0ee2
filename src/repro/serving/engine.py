"""Truly batched continuous-batching serving engine.

The paper's throughput argument (Fig. 4 dataflow) is that quantized weights
are streamed once per step *regardless of batch size*, so batching is what
amortizes the 3-bit weight traffic. This engine realizes that on the serving
side:

  * ONE shared slot-major cache — ``(slots, ...)`` batch layout with per-slot
    length counters — allocated once at construction (all three families:
    KV cache, SSM state, hybrid group state; all three weight forms: ``w``
    float, ``q`` levels, ``qp`` packed containers).
  * Admission is LENGTH-BUCKETED and batched: queued prompts are padded to a
    small set of power-of-two length buckets and every same-bucket request
    is prefilled in ONE jitted call (``prefill(..., lengths=)`` — families
    are padding-exact) and inserted with ONE jitted multi-slot scatter
    (``insert_prefill_many``). The prefill batch dimension is pinned to
    ``slots`` (short admissions are padded with dummy rows whose slot-map
    entry is out of range, so the scatter drops them), which bounds jit
    re-traces to O(#buckets) — not O(#distinct prompt lengths) — and keeps
    the 3-bit weight stream amortized across requests during admission,
    exactly as the decode tick amortizes it across slots. ``prefill_calls``
    counts batched prefill invocations the way ``decode_calls`` counts
    ticks.
  * ONE jitted ``decode_step`` per tick advances every active slot at once.
    Sampling and termination (budget exhausted / EOS) are computed on-device
    as masks; inactive slots are frozen in-graph (token and length held), so
    a tick never needs to know on the host which slots are live.
  * Results are drained asynchronously: each tick appends small device
    arrays to a pending buffer; tokens only cross to the host in bulk at
    ``drain()`` — there is no per-token host sync.

When ``eos_id`` is None request lifetimes are host-predictable (exactly
``max_new`` tokens), so admission needs no sync at all. ``run_all`` drains
every ``drain_every`` ticks — the async window: larger values sync less
often but hold more pending per-tick records; with EOS enabled the periodic
drain is also what discovers early-freed slots.

Quantized matmuls follow ``matmul_mode``: 'kernel' routes every serve-form
(``q``/``qp``) weight through the Pallas qmatvec/qmatmul kernels (weights
expanded only in VMEM — interpret mode off-TPU, for tests), 'dequant' uses
the fused levels-matmul fallback, 'auto' (default) picks 'kernel' on TPU.
In no serve mode does the decode graph materialize a dequantized weight
matrix.

Decode attention follows ``attn_mode`` the same way: 'kernel' runs the
fused Pallas ``kernels.attn_decode`` kernel (QK^T -> online softmax -> PV
in VMEM, per-slot valid-length block skipping), 'ref' the einsum path,
'auto' kernel on TPU. ``kv_bits=8`` stores the shared KV cache as int8 +
per-token scales — half the cache bytes per slot, so a fixed cache budget
holds twice the slots — for the transformer family AND hybrid; the decode
paths read the int8 cache directly (scales fused into attention).

Speculative decoding (``spec_k >= 1``) changes the tick from "one token"
to "up to spec_k+1 tokens": a quantized DRAFTER (by default the packed
3-bit ``qp`` export of the target's own weights — ``api.draft_of``) runs
``spec_k`` cheap ``decode_step`` proposals through the very same fused
kernel path, the target scores all of them plus a bonus position in ONE
multi-token ``verify_step``, vectorized acceptance-rejection keeps the
longest target-consistent prefix (exact target distribution at any
temperature; token-identical to non-spec greedy at T=0), and
``rollback_cache`` rewinds both caches past the rejected suffix — all
inside the SAME single jitted tick, so there is still no per-token (or
per-draft-token) host sync. Per-slot acceptance lengths fold into the
existing on-device active/emitted/budget masks; host bookkeeping only
learns token counts at ``drain()``. Families: dense/moe/hybrid (``ssm``
rejects spec mode loudly — SSD state can't rewind), and for sliding-window
archs the engine requires ``max_len <= window`` so speculation never
wraps the KV ring (a wrapped rewind would lose overwritten entries).

Overload hardening (``serving.resilience``): admission is BOUNDED —
``queue_limit`` + ``shed_policy`` turn ``submit()`` into a structured
accept/shed outcome with ``shed_count``/queue-depth counters instead of an
unbounded queue; per-request DEADLINES (``submit(..., deadline_ticks=)`` /
engine ``default_deadline``) cancel expired requests mid-stream on the
host side (slot freed and zeroed, partial output returned with
``Request.status == "deadline"``); slot PREEMPTION (``preempt_after``)
snapshots a long-running slot's committed tokens when the queue has
waiters, frees the slot, and requeues the request through the normal
bucketed prefill path (token-parity-exact at T=0 — greedy continuation
from prompt+committed is the unpreempted continuation); an on-device
HEALTH CHECK folded into every jitted tick (one per-slot isfinite
reduction riding the existing ``_pending`` drain — no extra sync)
quarantines slots whose logits go non-finite (``status == "poisoned"``,
row zeroed, ``poisoned_count``) instead of silently emitting garbage; a
DEGRADATION LADDER retries a failed tick call on progressively simpler
graphs (spec -> plain tick, kernel -> dequant/ref); and ``run_all(
max_ticks=)`` is a WATCHDOG that raises a diagnostic dump instead of
spinning forever. A deterministic ``resilience.FaultPlan`` (test-only
``fault_plan=`` hook) injects NaN logits / tick failures / admission
delays so every recovery path is exercised by tests and CI.

Durability (``serving.durability`` + ``checkpoint.integrity``): periodic
SNAPSHOTS (``snapshot_dir``/``snapshot_every``, or explicit
``snapshot()``) persist the complete engine state — device cache trees,
per-slot vectors, the sampling RNG key, and all host bookkeeping — as
atomic restore points; a WRITE-AHEAD JOURNAL (``journal=``) logs
submit/admit/commit/finish/shed events per tick so ``recover()`` on a
fresh engine restores the latest snapshot and resubmits the journal tail
(uids/deadlines preserved — at T=0 the recomputed stream is
token-identical, so a crash at ANY tick loses no accepted tokens); and a
WEIGHT-INTEGRITY probe (``integrity_every``, optional ``golden_dir``)
runs a cheap in-graph canary fingerprint over the packed
``qp``/``q``/``delta`` containers every N ticks, detecting any single-bit
soft error in the resident store (``FaultPlan.flip_bits`` injects them)
and SELF-HEALING: the corrupt container is reloaded from its golden copy
and every request whose tokens could have touched the corrupt weights is
rewound to its prompt and requeued through normal admission.

Caveat: for the ``moe`` family, expert-capacity dropping couples batch rows
— a slot's tokens can depend on what else is in the batch. Dynamic
activation scales (``policy.act_bits``) are per-ROW (each batch row gets
its own absmax), so decode ticks are row-independent; batched-prefill
parity under act quant additionally requires the prompt to land exactly on
its admission bucket (padding positions inside a row enter that row's
absmax) — and speculative verify processes spec_k+1 positions per row, so
spec parity likewise needs ``act_bits=None``. Preemption parity inherits
the same condition: the requeued request re-enters through batched prefill
at an arbitrary (mid-stream) length, so with act quant its re-admission
absmax differs from the original admission's and the continuation can
drift; with weight-only quantization the preempted continuation is
token-identical. Dense/ssm/hybrid decode AND
batched prefill with weight-only quantization are row-independent and
therefore token-identical to single-request ``generate``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.precision import QuantPolicy
from repro.models import api as model_api
from repro.models import get_model
from repro.serving import resilience
from repro.serving.resilience import (FaultPlan, SubmitOutcome,
                                      SubmitRejected, WatchdogExpired)

__all__ = ["generate", "Request", "ServingEngine", "FaultPlan",
           "SubmitOutcome", "SubmitRejected", "WatchdogExpired"]

# smallest admission bucket: prompts of length 1..8 share one compilation
_MIN_BUCKET = 8

# every engine counter, a plain int attribute: ``counters()`` reads them,
# the watchdog's diagnostics report them, snapshots carry them
COUNTERS = (
    "decode_calls", "prefill_calls", "admitted", "prefill_tokens",
    "prefill_positions", "live_slot_ticks", "spec_drafted", "spec_accepted",
    "shed_count", "deadline_miss_count", "preempt_count", "poisoned_count",
    "queue_peak", "snapshots_written", "journal_events", "replayed_events",
    "integrity_probes", "heal_count",
)


def _sample(key, logits: jnp.ndarray, temperature: float) -> jnp.ndarray:
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def _attn_kwargs(cfg: ModelConfig, attn_mode: str,
                 kv_bits: Optional[int]) -> Dict[str, Dict[str, Any]]:
    """Validated per-call kwargs for the attention serving knobs.

    ``attn_mode`` goes to ``decode_step`` AND ``prefill`` (the blocked
    Pallas prefill kernel covers admission; ``verify_step`` picks it up via
    the decode kwargs), ``kv_bits=8`` turns into
    ``prefill(quantize_cache=True)`` — all only for the attention-bearing
    families; ``ssm`` takes neither (no attention, no KV cache), and
    asking it to quantize one is a config error, not a silent no-op.
    """
    from repro.models.attention import ATTN_MODES, resolve_attn_mode
    if attn_mode not in ATTN_MODES:
        raise ValueError(f"attn_mode must be one of {ATTN_MODES}, "
                         f"got {attn_mode!r}")
    resolve_attn_mode(attn_mode)           # fail fast on bad explicit modes
    if kv_bits not in (None, 8):
        raise ValueError(f"kv_bits must be None or 8, got {kv_bits!r}")
    if cfg.family == "ssm":
        if kv_bits:
            raise ValueError("kv_bits=8 is meaningless for family 'ssm': "
                             "it has no KV cache to quantize")
        return {"prefill": {}, "decode": {}}
    pf: Dict[str, Any] = {"attn_mode": attn_mode}
    if kv_bits == 8:
        pf["quantize_cache"] = True
    return {"prefill": pf, "decode": {"attn_mode": attn_mode}}


def generate(params, prompts: jnp.ndarray, cfg: ModelConfig, *,
             policy: QuantPolicy, deltas=None, max_new_tokens: int = 32,
             temperature: float = 0.0, seed: int = 0,
             dtype=jnp.bfloat16, matmul_mode: str = "auto",
             attn_mode: str = "auto", kv_bits: Optional[int] = None,
             spec_k: int = 0, draft_params=None,
             draft_cfg: Optional[ModelConfig] = None) -> jnp.ndarray:
    """prompts (B, P) int32 -> (B, P + max_new_tokens). jit-compiled decode.

    ``attn_mode`` picks the attention implementation on every serving path
    — prefill admission and speculative verify (blocked online-softmax
    ``kernels.attn_prefill`` vs chunked/einsum ref) as well as per-token
    decode (fused ``kernels.attn_decode`` vs einsum ref); 'auto' takes the
    kernels on TPU. ``kv_bits=8`` serves from an int8 KV cache. Both knobs
    apply only to the attention-bearing families (``ssm`` ignores
    ``attn_mode`` and rejects ``kv_bits``).

    ``spec_k >= 1`` enables speculative decoding: ``draft_params`` (default:
    the packed-3-bit ``api.draft_of`` export of ``params``) proposes spec_k
    tokens per step and the target verifies them in one multi-token pass —
    same output distribution, token-identical at T=0, fewer target passes.
    The whole decode is one jitted ``lax.while_loop`` (no per-token sync).
    ``ssm`` rejects spec mode (SSD state can't rewind)."""
    if spec_k:
        return _spec_generate(params, prompts, cfg, policy=policy,
                              deltas=deltas, max_new_tokens=max_new_tokens,
                              temperature=temperature, seed=seed, dtype=dtype,
                              matmul_mode=matmul_mode, attn_mode=attn_mode,
                              kv_bits=kv_bits, spec_k=spec_k,
                              draft_params=draft_params, draft_cfg=draft_cfg)
    mod = get_model(cfg)
    b, p = prompts.shape
    max_len = p + max_new_tokens
    attn_kw = _attn_kwargs(cfg, attn_mode, kv_bits)
    logits, cache = mod.prefill(params, {"tokens": prompts}, cfg,
                                policy=policy, deltas=deltas, dtype=dtype,
                                max_len=max_len, matmul_mode=matmul_mode,
                                **attn_kw["prefill"])
    # independent streams: k0 samples the prefill token, the rest drive the
    # scan (sampling with `key` AND scanning over split(key, n) would reuse
    # the same randomness for tok0 and step 0)
    k0, key = jax.random.split(jax.random.PRNGKey(seed))
    tok0 = _sample(k0, logits[:, 0], temperature)[:, None].astype(jnp.int32)
    if max_new_tokens == 1:
        return jnp.concatenate([prompts, tok0], axis=1)

    @jax.jit
    def step(carry, k):
        cache, tok = carry
        logits, cache = mod.decode_step(params, cache, tok, cfg, policy=policy,
                                        deltas=deltas, dtype=dtype,
                                        matmul_mode=matmul_mode,
                                        **attn_kw["decode"])
        nxt = _sample(k, logits[:, 0], temperature)[:, None].astype(jnp.int32)
        return (cache, nxt), nxt

    (cache, _), toks = jax.lax.scan(step, (cache, tok0),
                                    jax.random.split(key, max_new_tokens - 1))
    out = jnp.concatenate([prompts, tok0, toks[:, :, 0].T], axis=1)
    return out


def _no_ring_wrap(mod, cfg: ModelConfig, max_len: int):
    """Speculative rollback is a length rewind: a sliding-window ring that
    wraps during the verify window would have overwritten live entries no
    rewind can restore. Forbid the configuration instead of corrupting."""
    if (hasattr(mod, "cache_len_for")
            and mod.cache_len_for(cfg, max_len) < max_len):
        raise ValueError(
            f"speculative decoding needs max_len <= sliding_window "
            f"({cfg.sliding_window}) for {cfg.name}: a wrapped KV ring "
            f"cannot be rolled back (got max_len {max_len})")


def _spec_models(params, cfg: ModelConfig, draft_params, draft_cfg):
    """Resolve the (target, drafter) pair; derive the drafter from the
    target checkpoint when none is given. Validates rollback capability."""
    if cfg.family == "ssm":
        raise ValueError("speculative decoding is unavailable for family "
                         "'ssm': the SSD state folds every token "
                         "irreversibly, so rejected drafts can't be rewound")
    if draft_params is None:
        draft_cfg, draft_params = model_api.draft_of(cfg, params)
    else:
        draft_cfg = draft_cfg or cfg
    if draft_cfg.family == "ssm":
        raise ValueError("the speculative DRAFTER can't be family 'ssm': "
                         "its state can't be rewound past rejected drafts")
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(f"draft vocab {draft_cfg.vocab_size} != target "
                         f"vocab {cfg.vocab_size}")
    return draft_params, draft_cfg


def _spec_generate(params, prompts: jnp.ndarray, cfg: ModelConfig, *,
                   policy: QuantPolicy, deltas, max_new_tokens: int,
                   temperature: float, seed: int, dtype, matmul_mode: str,
                   attn_mode: str, kv_bits: Optional[int], spec_k: int,
                   draft_params, draft_cfg: Optional[ModelConfig]):
    """Speculative ``generate``: one jitted ``lax.while_loop`` whose body is
    the shared ``spec_decode_tick``; each iteration commits a variable
    1..spec_k+1 tokens per row into a fixed output buffer."""
    from repro.serving.spec import emit_counts, spec_decode_tick
    draft_params, draft_cfg = _spec_models(params, cfg, draft_params,
                                           draft_cfg)
    mod, dmod = get_model(cfg), get_model(draft_cfg)
    b, p = prompts.shape
    # verify scratch-writes up to spec_k+1 positions past the committed
    # stream; size the cache so the last in-budget tick stays in bounds
    max_len = p + max_new_tokens + spec_k
    _no_ring_wrap(mod, cfg, max_len)
    _no_ring_wrap(dmod, draft_cfg, max_len)
    attn_kw = _attn_kwargs(cfg, attn_mode, kv_bits)
    dattn_kw = _attn_kwargs(draft_cfg, attn_mode, kv_bits)
    mkw = dict(policy=policy, deltas=deltas, dtype=dtype,
               matmul_mode=matmul_mode)
    dmkw = dict(policy=policy, deltas=None, dtype=dtype,
                matmul_mode=matmul_mode)
    logits, cache = mod.prefill(params, {"tokens": prompts}, cfg,
                                max_len=max_len, **mkw, **attn_kw["prefill"])
    _, dcache = dmod.prefill(draft_params, {"tokens": prompts}, draft_cfg,
                             max_len=max_len, **dmkw, **dattn_kw["prefill"])
    k0, key = jax.random.split(jax.random.PRNGKey(seed))
    tok0 = _sample(k0, logits[:, 0], temperature)[:, None].astype(jnp.int32)
    if max_new_tokens == 1:
        return jnp.concatenate([prompts, tok0], axis=1)
    # rollback writes per-row lengths; normalize up front so the while_loop
    # carry keeps one structure
    cache["len"] = jnp.broadcast_to(cache["len"], (b,)).astype(jnp.int32)
    dcache["len"] = jnp.broadcast_to(dcache["len"], (b,)).astype(jnp.int32)
    outbuf = jnp.zeros((b, max_new_tokens), jnp.int32).at[:, 0].set(tok0[:, 0])
    budget = jnp.full((b,), max_new_tokens, jnp.int32)
    rows = jnp.arange(b)
    t1 = spec_k + 1

    def cond(carry):
        return jnp.any(carry[3] < max_new_tokens)

    def body(carry):
        cache, dcache, pending, emitted, buf, key = carry
        key, kt = jax.random.split(key)
        active = emitted < max_new_tokens
        cache, dcache, a, out, pending, _ok = spec_decode_tick(
            mod, dmod, params, draft_params, cfg, draft_cfg, cache, dcache,
            pending, active, spec_k=spec_k, temperature=temperature, key=kt,
            mkw=mkw, dmkw=dmkw, attn_kw=attn_kw["decode"],
            dattn_kw=dattn_kw["decode"])
        n, _ = emit_counts(out, a, active=active, emitted=emitted,
                           budget=budget, eos_id=-1)
        for j in range(t1):
            # rows past their window park the write at the OOB sentinel
            idx = jnp.where(j < n, emitted + j, max_new_tokens)
            buf = buf.at[rows, idx].set(out[:, j], mode="drop")
        return cache, dcache, pending, emitted + n, buf, key

    run = jax.jit(lambda c: jax.lax.while_loop(cond, body, c))
    carry = run((cache, dcache, tok0, jnp.ones((b,), jnp.int32), outbuf, key))
    return jnp.concatenate([prompts, carry[4]], axis=1)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # per-request serving stats, filled at drain time: decode ticks this
    # request participated in, and the histogram {window length -> count}
    # of tokens emitted per tick (always {1: n} without speculation; the
    # draft-accept length distribution with it)
    ticks: int = 0
    accept_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # resilience: terminal outcome (one of resilience.STATUS — "ok" unless
    # the request was cancelled/shed/quarantined), absolute expiry in
    # decode ticks (None = no deadline), times preempted, and host
    # wall-clock stamps for submit->finish latency
    status: str = "ok"
    deadline_at: Optional[int] = None
    preemptions: int = 0
    submit_time: float = 0.0
    finish_time: float = 0.0

    @property
    def admit_prompt(self) -> List[int]:
        """What admission prefills: the prompt plus every committed token.
        For a fresh request this is the prompt; a preempted request
        re-enters the bucketed prefill path with its progress folded in,
        which at T=0 greedy makes the continuation token-identical to the
        run it was evicted from."""
        return self.prompt + self.out

    @property
    def remaining(self) -> int:
        """Tokens still owed (the admission budget after preemption)."""
        return self.max_new - len(self.out)


class ServingEngine:
    """Slot-based continuous batching: one jitted decode per tick, all slots.

    ``step()`` = admit + one batched tick (async — tokens stay on device);
    ``drain()`` = bulk host transfer of everything emitted since the last
    drain; ``run_all()`` = drive until queue and slots are empty.

    ``decode_calls`` counts ticks — each is exactly one ``decode_step``
    invocation regardless of the number of active slots — and
    ``prefill_calls`` counts admissions the same way: all queued requests
    sharing a length bucket enter through ONE jitted batched prefill + ONE
    jitted multi-slot admit (asserted by tests/test_engine_batched.py and
    tests/test_engine_bucketed.py).

    Admission order is FIFO by bucket: each admission round serves the
    oldest queued request's bucket, and other same-bucket requests ride
    along (bounded queue-jumping in exchange for batched prefill).

    Instrumentation: ``counters()`` returns every counter in ``COUNTERS``,
    among them ``prefill_tokens`` over ``prefill_positions`` (the useful
    share of admission prefill) and ``live_slot_ticks`` (occupied slots per
    tick, summed). The host spans ``serve.admit`` (args ``bucket``,
    ``rows``), ``serve.tick``, ``serve.sync.wait`` (the blocking
    ``device_get``) and ``serve.sync.host`` (attribution) are
    ``jax.profiler.TraceAnnotation``s: a profiler trace holds them beside
    the device ops, on the same clock. The tick's sampling runs under the
    ``tick.sample`` name scope, the model's parts under ``model.*``.
    """

    def __init__(self, params, cfg: ModelConfig, *, policy: QuantPolicy,
                 deltas=None, slots: int = 8, max_len: int = 512,
                 dtype=jnp.bfloat16, temperature: float = 0.0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 drain_every: int = 4, matmul_mode: str = "auto",
                 attn_mode: str = "auto", kv_bits: Optional[int] = None,
                 spec_k: int = 0, draft_params=None,
                 draft_cfg: Optional[ModelConfig] = None,
                 attn_chunk: int = 1024,
                 queue_limit: Optional[int] = None,
                 shed_policy: str = "reject",
                 default_deadline: Optional[int] = None,
                 preempt_after: Optional[int] = None,
                 max_ticks: Optional[int] = None, degrade: bool = True,
                 fault_plan: Optional[FaultPlan] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 journal: Optional[str] = None,
                 integrity_every: Optional[int] = None,
                 golden_dir: Optional[str] = None):
        from repro.core.quant_dense import MATMUL_MODES
        if matmul_mode not in MATMUL_MODES:
            raise ValueError(f"matmul_mode must be one of {MATMUL_MODES}, "
                             f"got {matmul_mode!r}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if shed_policy not in resilience.SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of "
                             f"{resilience.SHED_POLICIES}, got {shed_policy!r}")
        for name, val in (("queue_limit", queue_limit),
                          ("default_deadline", default_deadline),
                          ("preempt_after", preempt_after),
                          ("max_ticks", max_ticks),
                          ("snapshot_every", snapshot_every),
                          ("integrity_every", integrity_every)):
            if val is not None and val < 1:
                raise ValueError(f"{name} must be >= 1 or None, got {val}")
        self.params, self.cfg, self.policy = params, cfg, policy
        self.deltas, self.dtype = deltas, dtype
        self.mod = get_model(cfg)
        self.slots, self.max_len = slots, max_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.drain_every = max(1, drain_every)
        self.matmul_mode = matmul_mode
        # attention dispatch (prefill admission + verify + decode kernels
        # vs ref paths) + int8 KV cache (attention families): kv_bits=8
        # halves cache bytes per slot, i.e. doubles the slots a fixed cache
        # budget can hold — validated (ssm raises) in one place.
        # attn_chunk bounds the ref-mode prefill working set per KV chunk —
        # the long-prompt admission knob when the kernel isn't available
        self.attn_mode, self.kv_bits = attn_mode, kv_bits
        self.attn_chunk = attn_chunk
        self._attn_kw = _attn_kwargs(cfg, attn_mode, kv_bits)
        # shared slot-major cache, allocated ONCE
        self.cache = model_api.init_cache(cfg, slots, max_len, dtype,
                                          per_slot_len=True, kv_bits=kv_bits)
        # speculative decoding: a second slot-major cache for the DRAFTER
        # (by default the qp export of the target's own weights), sharing
        # the engine's serving knobs; spec_accept_rate counters ride drain
        self.spec_k = int(spec_k)
        self._spec = self.spec_k > 0
        self.spec_drafted = 0                 # draft proposals scored
        self.spec_accepted = 0                # proposals the target kept
        if self._spec:
            draft_params, draft_cfg = _spec_models(params, cfg, draft_params,
                                                   draft_cfg)
            _no_ring_wrap(self.mod, cfg, max_len)
            self.draft_params, self.draft_cfg = draft_params, draft_cfg
            self.dmod = get_model(draft_cfg)
            _no_ring_wrap(self.dmod, draft_cfg, max_len)
            self._dattn_kw = _attn_kwargs(draft_cfg, attn_mode, kv_bits)
            self.draft_cache = model_api.init_cache(
                draft_cfg, slots, max_len, dtype, per_slot_len=True,
                kv_bits=kv_bits)
        # per-slot device state
        self._tokens = jnp.zeros((slots, 1), jnp.int32)    # last emitted token
        self._active = jnp.zeros((slots,), bool)
        self._emitted = jnp.zeros((slots,), jnp.int32)     # tokens produced
        self._budget = jnp.zeros((slots,), jnp.int32)      # per-slot max_new
        self._key = jax.random.PRNGKey(seed)
        # the healthy poison bias: ALWAYS a tick input, so fault injection
        # (NaN entries) never changes the traced graph
        self._poison0 = jnp.zeros((slots,), jnp.float32)
        # host-side bookkeeping
        self.queue: List[Request] = []
        self._slot_req: List[Optional[Request]] = [None] * slots
        self._ticks_left = [0] * slots        # deterministic lifetime bound
        self._slot_ticks = [0] * slots        # ticks the current owner held
        # pending records: (tokens (slots, T), counts (slots,), done,
        # owners, accepted-or-None, kind, bad-or-None) — T=1 with counts as
        # the emitted mask for admissions and plain ticks, T=spec_k+1 with
        # true counts for speculative ticks; ``bad`` is the tick's on-device
        # per-slot health flag (None for admissions)
        self._pending: List[Tuple] = []
        self._finished: List[Request] = []    # synced but not yet returned
        self._uid = 0
        self.decode_calls = 0                 # ticks == decode_step calls
        self.prefill_calls = 0                # batched prefill invocations
        # admission and occupancy: requests admitted (a preempted request
        # again on re-entry), their prefilled tokens, the positions prefill
        # computed for them (padding rows and columns included), and the
        # occupied slots at each tick dispatch, summed
        self.admitted = 0
        self.prefill_tokens = 0
        self.prefill_positions = 0
        self.live_slot_ticks = 0
        # resilience knobs + counters
        self.queue_limit = queue_limit
        self.shed_policy = shed_policy
        self.default_deadline = default_deadline
        self.preempt_after = preempt_after
        self.max_ticks = max_ticks
        self.degrade = degrade
        self._fault_plan = fault_plan
        self._failed_ticks: set = set()       # one-shot fail_ticks consumed
        self._was_spec = False                # degraded out of spec mode
        self.shed_count = 0                   # requests refused/evicted
        self.deadline_miss_count = 0          # requests expired past deadline
        self.preempt_count = 0                # slot evictions (requeued)
        self.poisoned_count = 0               # slots quarantined (non-finite)
        self.fallback_events: List[Tuple[int, str]] = []  # (tick, ladder step)
        self.queue_peak = 0                   # high-water queue depth
        # durability: periodic snapshots + write-ahead journal (see
        # serving.durability) and the weight-store integrity probe + heal
        # (see checkpoint.integrity)
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.integrity_every = integrity_every
        self.golden_dir = golden_dir
        self.snapshots_written = 0            # snapshot() completions
        self.journal_events = 0               # events appended to the WAL
        self.replayed_events = 0              # journal events replayed in
        self.integrity_probes = 0             # canary passes run
        self.heal_count = 0                   # containers reloaded from golden
        self._last_snapshot_tick = -1         # don't re-snapshot a tick
        self._crashed_ticks: set = set()      # one-shot crash_at_tick consumed
        self._flipped_ticks: set = set()      # one-shot flip_bits consumed
        if journal is not None and not hasattr(journal, "append"):
            from repro.serving.durability import Journal
            journal = Journal(journal)
        self._journal = journal
        self._probe_paths: Optional[List[str]] = None
        if integrity_every is not None:
            self._init_integrity()
        # admission buckets are capped by the cache length: for sliding-
        # window archs the ring slice in prefill is only per-row-exact while
        # padded length <= window, so longer prompts take the solo path
        self._bucket_cap = (self.mod.cache_len_for(cfg, max_len)
                            if hasattr(self.mod, "cache_len_for") else max_len)
        self._build_jits()

    def _build_jits(self):
        """(Re)build every jitted serving graph from the CURRENT mode knobs
        (spec on/off, matmul_mode, attn_mode). Called at construction and
        again by each degradation-ladder step — the mode kwargs are baked
        into the traced graphs, so changing them means re-jitting.

        Donation: the shared cache(s) are donated (without donation every
        tick and every admission materializes a full second copy of the
        slot-major cache). The small per-slot vectors are NOT donated —
        pending records hold references to pre-tick ``active`` arrays."""
        if self._spec:
            self._tick_fn = jax.jit(self._spec_tick, donate_argnums=(2, 3))
            self._prefill_draft_fn = jax.jit(self._prefill_draft)
            self._admit_draft_fn = jax.jit(
                lambda dc, slot, src: self.dmod.insert_prefill(dc, slot, src),
                donate_argnums=(0,))
            self._admit_draft_many_fn = jax.jit(
                lambda dc, sm, src: self.dmod.insert_prefill_many(dc, sm,
                                                                  src),
                donate_argnums=(0,))
            self._free_draft_fn = jax.jit(
                lambda dc, idx: model_api.free_slots(self.draft_cfg, dc, idx),
                donate_argnums=(0,))
        else:
            self._tick_fn = jax.jit(self._tick, donate_argnums=(1,))
        self._admit_fn = jax.jit(self._admit_device, donate_argnums=(1,))
        self._admit_many_fn = jax.jit(self._admit_many, donate_argnums=(0,))
        self._prefill_fn = jax.jit(self._prefill)
        # slot release (preemption / deadline cancel / quarantine): index
        # vector is always padded to (slots,) with the OOB sentinel so it
        # compiles once regardless of how many rows are freed
        self._free_fn = jax.jit(
            lambda c, idx: model_api.free_slots(self.cfg, c, idx),
            donate_argnums=(0,))
        # the analysis registry's window into this engine: the jitted fns,
        # so trace/retrace budgets can be reported from the same place the
        # contract passes run — repro.analysis.contracts.retrace_report
        # reads trace_counts()
        self._jits = {"tick": self._tick_fn, "prefill": self._prefill_fn,
                      "admit": self._admit_fn, "admit_many": self._admit_many_fn,
                      "free": self._free_fn}
        if self._spec:
            self._jits.update(prefill_draft=self._prefill_draft_fn,
                              admit_draft=self._admit_draft_fn,
                              admit_draft_many=self._admit_draft_many_fn)

    # --- degradation ladder (called via resilience.degrade_step) ------------

    def _disable_spec(self):
        """Ladder step 1, spec -> plain: abandon the drafter and its cache
        and re-jit the plain tick. The target stream is unaffected (spec is
        exact — dropping it changes throughput, never tokens): the device
        ``_tokens`` row is the last committed-but-unfed token in both
        modes, so the plain tick resumes mid-request seamlessly. Host
        ``_ticks_left`` stays an upper bound (spec emits >= 1 token per
        tick), and ``_was_spec`` keeps ``_spin_up`` syncing so early
        finishes discovered at drain still free slots promptly."""
        self._spec = False
        self._was_spec = True
        self.spec_k = 0
        self.draft_cache = None
        self._build_jits()

    def _fallback_modes(self):
        """Ladder step 2, kernel -> fallback: route every quantized matmul
        through the fused dequant path and every attention through the
        ref path — the parity oracles the kernels are tested against —
        then re-jit."""
        self.matmul_mode = "dequant"
        self.attn_mode = "ref"
        self._attn_kw = _attn_kwargs(self.cfg, self.attn_mode, self.kv_bits)
        if self._spec:
            self._dattn_kw = _attn_kwargs(self.draft_cfg, self.attn_mode,
                                          self.kv_bits)
        self._build_jits()

    # --- durability: snapshots, write-ahead journal, weight integrity -------

    def _log_event(self, event: Dict[str, Any]):
        """Append one event to the write-ahead journal (no-op without
        one). Every event carries the current tick."""
        if self._journal is not None:
            self._journal.append(dict(event, tick=self.decode_calls))
            self.journal_events += 1

    def snapshot(self, snapshot_dir: Optional[str] = None) -> str:
        """Persist complete engine state (device trees + host bookkeeping)
        as an atomic restore point; see ``serving.durability``."""
        from repro.serving import durability
        d = snapshot_dir or self.snapshot_dir
        if d is None:
            raise ValueError("no snapshot_dir: pass one here or at "
                             "construction")
        return durability.snapshot_engine(self, d)

    def restore(self, snapshot_dir: Optional[str] = None,
                step: Optional[int] = None) -> Dict[str, Any]:
        """Load a snapshot into this (freshly constructed) engine and
        resume exactly where it was taken — token-identical at T=0."""
        from repro.serving import durability
        d = snapshot_dir or self.snapshot_dir
        if d is None:
            raise ValueError("no snapshot_dir: pass one here or at "
                             "construction")
        return durability.restore_engine(self, d, step)

    def recover(self, snapshot_dir: Optional[str] = None,
                journal: Optional[str] = None) -> Dict[str, Any]:
        """Crash recovery: latest snapshot (if any) + journal-tail replay.
        Defaults to the construction-time snapshot dir and journal path."""
        from repro.serving import durability
        jpath = journal or (self._journal.path if self._journal is not None
                            else None)
        return durability.recover(
            self, snapshot_dir=snapshot_dir or self.snapshot_dir,
            journal=jpath)

    def _init_integrity(self):
        """Build the weight-store integrity machinery: the protected-path
        list (packed ``qp``/``q``/``delta`` containers for serve forms,
        every leaf for float masters), a jitted canary-fingerprint probe,
        the golden fingerprint vector, and an independent host-side golden
        copy + CRC manifest to heal from. ``golden_dir`` additionally
        persists the golden store to disk (checkpoint.integrity.save_golden)
        so heals survive the process too."""
        from repro.checkpoint import integrity
        from repro.core.treeutil import tree_get
        paths = integrity.protected_paths(self.params)
        self._probe_paths, probe = integrity.make_probe(self.params, paths)
        self._probe_fn = jax.jit(probe)
        self._golden = {p: np.array(np.asarray(tree_get(self.params, p)))
                        for p in paths}
        self._manifest = integrity.build_manifest(self.params, paths)
        self._golden_fp = np.asarray(self._probe_fn(self.params))
        if self.golden_dir is not None:
            integrity.save_golden(self.golden_dir, self.params, paths)
        self._next_probe = 0

    def _flip_bit(self, path: str, bit: int):
        """Fault injection: XOR one bit of the params leaf at ``path`` —
        a soft error in the resident weight store (``bit`` wraps modulo
        the leaf's bit count). Host round-trip, so the device copy is
        replaced wholesale; the golden copy is independent."""
        from repro.core.treeutil import tree_get, tree_set
        a = np.array(np.asarray(tree_get(self.params, path)))
        raw = a.view(np.uint8).reshape(-1)
        b = int(bit) % (raw.size * 8)
        raw[b // 8] ^= np.uint8(1 << (b % 8))
        self.params = tree_set(self.params, path, jnp.asarray(a))

    def _integrity_probe(self):
        """One canary pass over the protected weight leaves: fingerprint
        vector vs golden. A mismatch names the corrupt container(s) and
        triggers the self-heal."""
        self.integrity_probes += 1
        fps = np.asarray(self._probe_fn(self.params))
        bad = [self._probe_paths[i]
               for i in np.nonzero(fps != self._golden_fp)[0]]
        if bad:
            self._heal(bad)

    def _heal(self, bad_paths: List[str]):
        """Self-heal detected weight corruption: reload each corrupt
        container from the golden copy, confirm the probe matches golden
        again, then REWIND every request whose tokens could have been
        computed against the corrupt store — the suspect window is
        everything since the last clean probe, so resident unfinished
        requests and ok-finished-but-undrained requests are rolled back to
        their prompt and requeued through the normal bucketed admission
        path (same machinery as preemption; at T=0 the recomputed stream
        is the clean stream). Requests already DRAINED between the clean
        probe and detection are the caller-visible at-risk window: probe
        at least as often as you drain to close it."""
        from repro.core.treeutil import tree_set
        self._sync()
        for p in bad_paths:
            self.params = tree_set(self.params, p,
                                   jnp.asarray(self._golden[p]))
            self.heal_count += 1
            self.fallback_events.append((self.decode_calls, f"heal:{p}"))
        fps = np.asarray(self._probe_fn(self.params))
        if not np.array_equal(fps, self._golden_fp):
            raise RuntimeError(
                f"integrity heal failed: {bad_paths} still mismatch the "
                f"golden fingerprints after reload — golden copy corrupt?")
        self._log_event({"e": "heal", "paths": list(bad_paths)})
        victims = [s for s in range(self.slots)
                   if (r := self._slot_req[s]) is not None and not r.done]
        resurrect = [r for r in self._finished if r.status == "ok"]
        self._finished = [r for r in self._finished if r.status != "ok"]
        requeue = [self._slot_req[s] for s in victims] + resurrect
        for s in victims:
            self._release_slot(s)
        for r in sorted(requeue, key=lambda r: r.uid):
            r.out.clear()
            r.done = False
            r.status = "ok"
            r.ticks = 0
            r.accept_hist = {}
            r.finish_time = 0.0
            self.queue.append(r)
        if victims:
            self._deactivate(victims)
            self._free_rows(victims)

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of draft proposals the target accepted (drain-synced;
        the ``prefill_calls``-style speculative counter)."""
        return self.spec_accepted / self.spec_drafted if self.spec_drafted \
            else 0.0

    def counters(self) -> Dict[str, int]:
        """Every engine counter (``COUNTERS``) by name."""
        return {k: int(getattr(self, k)) for k in COUNTERS}

    # --- static-analysis surface (repro.analysis.contracts) -----------------

    def trace_counts(self) -> Dict[str, int]:
        """{jit name: compiled-trace count} for every jitted serving graph.

        The retrace-budget surface: a healthy engine compiles the tick
        ONCE for an entire run and the bucketed prefill O(#buckets) times.
        ``repro.analysis.contracts.retrace_report`` turns this into the
        same JSON the contract passes report in."""
        return {name: int(fn._cache_size())
                for name, fn in self._jits.items()}

    def contract_points(self, bucket: Optional[int] = None
                        ) -> List[Dict[str, Any]]:
        """The engine's jitted serving graphs, described abstractly for the
        static-analysis passes — NOTHING here executes a graph.

        Each point: ``name``; the unjitted ``fn``; example ``args``
        (engine state plus ShapeDtypeStructs where no live array exists);
        ``donate`` (the argnums the engine donates, for the donation
        pass); ``carry`` (input argnum -> output index for every buffer
        that must be an aval fixed point across ticks — the carry-dtype
        pass); and ``score_dims`` ((T, S) a quadratic score tensor would
        trail with, or None where the pass doesn't apply).

        ``bucket`` is the admission bucket length to describe prefill at
        (default: the largest, i.e. the cache-capped bucket)."""
        bucket = bucket or self._bucket_cap
        key = jax.random.PRNGKey(0)
        sds = jax.ShapeDtypeStruct
        toks = sds((self.slots, bucket), jnp.int32)
        lens = sds((self.slots,), jnp.int32)
        ivec = sds((self.slots,), jnp.int32)
        # abstract batched-prefill outputs feed the admission point
        logits0, src = jax.eval_shape(self._prefill, self.params, toks, lens)
        points: List[Dict[str, Any]] = []
        if self._spec:
            points.append(dict(
                name="spec_tick", fn=self._spec_tick,
                args=(self.params, self.draft_params, self.cache,
                      self.draft_cache, self._tokens, self._active,
                      self._emitted, self._budget, self._poison0, key),
                donate=(2, 3),
                carry={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
                score_dims=(self.spec_k + 1, self._bucket_cap)))
        else:
            points.append(dict(
                name="decode_tick", fn=self._tick,
                args=(self.params, self.cache, self._tokens, self._active,
                      self._emitted, self._budget, self._poison0, key),
                donate=(1,),
                carry={1: 0, 2: 1, 3: 2, 4: 3},
                score_dims=None))
        points.append(dict(
            name="prefill_bucketed", fn=self._prefill,
            args=(self.params, toks, lens), donate=(), carry={},
            score_dims=(bucket, bucket)))
        points.append(dict(
            name="admit_many", fn=self._admit_many,
            args=(self.cache, self._tokens, self._active, self._emitted,
                  self._budget, ivec, src, logits0, ivec, key),
            donate=(0,),
            carry={0: 0, 1: 1, 2: 2, 3: 3, 4: 4},
            score_dims=None))
        return points

    # --- jitted graph builders (self.mod looked up at trace time so tests can
    # --- instrument the family module's decode_step) ------------------------

    def _mkw(self) -> Dict[str, Any]:
        return dict(policy=self.policy, deltas=self.deltas, dtype=self.dtype,
                    matmul_mode=self.matmul_mode)

    def _eos(self) -> int:
        return -1 if self.eos_id is None else int(self.eos_id)  # -1 never hits

    def _prefill(self, params, toks, lengths=None):
        return self.mod.prefill(params, {"tokens": toks}, self.cfg,
                                max_len=self.max_len, lengths=lengths,
                                attn_chunk=self.attn_chunk,
                                **self._mkw(), **self._attn_kw["prefill"])

    def _dmkw(self) -> Dict[str, Any]:
        # the drafter serves its own (serve-form) params: target deltas
        # don't apply to it
        return dict(policy=self.policy, deltas=None, dtype=self.dtype,
                    matmul_mode=self.matmul_mode)

    def _prefill_draft(self, dparams, toks, lengths=None):
        return self.dmod.prefill(dparams, {"tokens": toks}, self.draft_cfg,
                                 max_len=self.max_len, lengths=lengths,
                                 attn_chunk=self.attn_chunk,
                                 **self._dmkw(), **self._dattn_kw["prefill"])

    def _tick(self, params, cache, tokens, active, emitted, budget, poison,
              key):
        """Advance every active slot one token. Masks computed on-device.

        ``poison`` (slots,) f32 is added to the logits before the health
        check — all-zeros in healthy operation (one add, graph identical),
        NaN entries under fault injection. ``bad`` flags active rows whose
        logits went non-finite: they are frozen exactly like inactive rows
        (token and length held, nothing emitted) and deactivated, and the
        flag rides the pending drain so the host can quarantine them — no
        extra sync, no sampling from a corrupt distribution."""
        logits, new_cache = self.mod.decode_step(params, cache, tokens,
                                                 self.cfg, **self._mkw(),
                                                 **self._attn_kw["decode"])
        with jax.named_scope("tick.sample"):
            logits = logits + poison[:, None, None]
            bad = active & ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
            ok = active & ~bad
            nxt = _sample(key, logits[:, 0],
                          self.temperature).astype(jnp.int32)
            nxt = jnp.where(ok, nxt, tokens[:, 0])   # freeze inactive + bad
            emitted = emitted + ok.astype(jnp.int32)
            done = ok & ((emitted >= budget) | (nxt == self._eos()))
            new_cache["len"] = jnp.where(ok, new_cache["len"], cache["len"])
        return new_cache, nxt[:, None], ok & ~done, emitted, done, bad

    def _spec_tick(self, params, dparams, cache, dcache, tokens, active,
                   emitted, budget, poison, key):
        """Advance every active slot by 1..spec_k+1 tokens: the shared
        ``spec_decode_tick`` core (draft chain -> one multi-token verify ->
        vectorized acceptance -> per-slot rollback of both caches) plus the
        engine's budget/EOS window truncation, all in this ONE jitted call.
        Inactive slots are frozen in-graph: their verify scratch-writes are
        fully rewound and their token/length held, exactly like the plain
        tick's masking. ``poison``/``bad`` mirror the plain tick's health
        check — the core treats a non-finite row as frozen (full rewind,
        nothing committed), so a poisoned slot emits nothing and both
        caches stay clean."""
        from repro.serving.spec import emit_counts, spec_decode_tick
        cache, dcache, a, out, new_tok, row_ok = spec_decode_tick(
            self.mod, self.dmod, params, dparams, self.cfg, self.draft_cfg,
            cache, dcache, tokens, active, spec_k=self.spec_k,
            temperature=self.temperature, key=key, mkw=self._mkw(),
            dmkw=self._dmkw(), attn_kw=self._attn_kw["decode"],
            dattn_kw=self._dattn_kw["decode"], logit_bias=poison)
        bad = active & ~row_ok
        eff = active & ~bad
        n, done = emit_counts(out, a, active=eff, emitted=emitted,
                              budget=budget, eos_id=self._eos())
        return (cache, dcache, new_tok, eff & ~done, emitted + n, done,
                out, n, jnp.where(eff, a, 0), bad)

    def _admit_device(self, params, cache, tokens, active, emitted, budget,
                      slot, src, logits0, req_budget, key):
        """Insert a prefilled request into ``slot`` and sample its first
        token. ``slot``/``req_budget`` traced -> compiles once."""
        cache = self.mod.insert_prefill(cache, slot, src)
        t0 = _sample(key, logits0[:, 0], self.temperature).astype(jnp.int32)
        tokens = jax.lax.dynamic_update_slice(tokens, t0[:, None], (slot, 0))
        # the prefill sample already counts: a max_new==1 request (or an
        # immediate EOS) never becomes active
        act0 = (req_budget > 1) & (t0[0] != self._eos())
        active = jax.lax.dynamic_update_slice(active, act0[None], (slot,))
        emitted = jax.lax.dynamic_update_slice(
            emitted, jnp.ones((1,), jnp.int32), (slot,))
        budget = jax.lax.dynamic_update_slice(budget, req_budget[None], (slot,))
        return cache, tokens, active, emitted, budget

    def _admit_many(self, cache, tokens, active, emitted, budget, slot_map,
                    src, logits0, req_budget, key):
        """Insert an N-row batched prefill into slots ``slot_map`` and
        sample every row's first token — ONE jitted call for the whole
        admission round. Rows with ``slot_map[i] >= slots`` are batch
        padding: every scatter drops them (JAX OOB-scatter semantics)."""
        cache = self.mod.insert_prefill_many(cache, slot_map, src)
        t0 = _sample(key, logits0[:, 0], self.temperature).astype(jnp.int32)
        tokens = tokens.at[slot_map].set(t0[:, None], mode="drop")
        # the prefill sample already counts: a max_new==1 request (or an
        # immediate EOS) never becomes active
        act0 = (req_budget > 1) & (t0 != self._eos())
        active = active.at[slot_map].set(act0, mode="drop")
        emitted = emitted.at[slot_map].set(jnp.ones_like(req_budget),
                                           mode="drop")
        budget = budget.at[slot_map].set(req_budget, mode="drop")
        return cache, tokens, active, emitted, budget

    # --- public API ---------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int = 16,
               deadline_ticks: Optional[int] = None) -> SubmitOutcome:
        """Enqueue a request. Malformed requests raise ``SubmitRejected``
        (a ValueError with a machine-readable ``reason``); well-formed
        requests return a ``SubmitOutcome`` — the uid as an int (legacy
        callers unchanged) when admitted, falsy with
        ``reason='queue_full'`` when bounded admission sheds it.

        ``deadline_ticks`` (or the engine's ``default_deadline``) sets an
        absolute expiry ``decode_calls + deadline_ticks``: a request not
        finished by then is cancelled — mid-stream if resident (slot freed,
        partial output returned with ``status='deadline'``), or straight
        from the queue if it never got a slot."""
        if len(prompt) == 0:
            # a [] prompt would build a (1, 0) token array and crash deep
            # inside prefill; reject it where the caller can see why
            raise SubmitRejected("empty_prompt",
                                 "prompt must contain at least one token")
        if max_new < 1:
            raise SubmitRejected("bad_max_new",
                                 f"max_new must be >= 1, got {max_new}")
        if len(prompt) + max_new + self.spec_k > self.max_len:
            # speculative verify scratch-writes up to spec_k positions past
            # the final committed token; reserve that headroom in the cache
            total = len(prompt) + max_new + self.spec_k
            label = (f"prompt+max_new+spec_k ({len(prompt)}+{max_new}"
                     f"+{self.spec_k}={total})" if self._spec
                     else f"prompt+max_new ({total})")
            raise SubmitRejected(
                "too_long",
                f"{label} exceeds engine max_len {self.max_len}")
        if deadline_ticks is not None and deadline_ticks < 1:
            raise SubmitRejected(
                "bad_deadline",
                f"deadline_ticks must be >= 1, got {deadline_ticks}")
        shed: Tuple[int, ...] = ()
        if self.queue_limit is not None and len(self.queue) >= self.queue_limit:
            self.shed_count += 1
            if self.shed_policy == "reject":
                self._log_event({"e": "shed", "uid": None,
                                 "reason": "queue_full"})
                return SubmitOutcome(0, accepted=False, reason="queue_full")
            victim = self.queue.pop(0)               # drop_oldest
            self._log_event({"e": "shed", "uid": victim.uid,
                             "reason": "queue_full"})
            self._finish(victim, "shed")
            shed = (victim.uid,)
        self._uid += 1
        dl = deadline_ticks if deadline_ticks is not None \
            else self.default_deadline
        req = Request(self._uid, list(prompt), max_new,
                      deadline_at=(self.decode_calls + dl) if dl else None,
                      submit_time=time.perf_counter())
        # write-ahead: the acceptance is durable before the queue sees it,
        # so a crash after this line can always replay the request
        self._log_event({"e": "submit", "uid": req.uid, "prompt": req.prompt,
                         "max_new": max_new, "deadline_at": req.deadline_at})
        self.queue.append(req)
        self.queue_peak = max(self.queue_peak, len(self.queue))
        return SubmitOutcome(self._uid, accepted=True, shed=shed)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def _bucket_len(self, plen: int) -> int:
        """Admission bucket: next power of two >= plen (floor _MIN_BUCKET),
        capped at the cache length — a small static set, so jitted prefill
        re-traces O(#buckets) times under arbitrary mixed prompt lengths."""
        return min(max(_MIN_BUCKET, 1 << (plen - 1).bit_length()),
                   self._bucket_cap)

    def _free_slots(self) -> List[int]:
        return [s for s in range(self.slots) if self._slot_req[s] is None]

    def _occupied(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def _spin_up(self):
        """Admit queued requests into free slots, one length bucket at a
        time: every same-bucket queued request enters through ONE jitted
        batched prefill + ONE jitted multi-slot admit. When the queue has
        waiters and no slot is free, ``preempt_after`` lets a slot held
        longer than its fair-share tick budget be preempted (committed
        tokens snapshotted host-side, row freed, request requeued at the
        back — it re-enters right here through the same bucketed path).

        Admission keys on ``admit_prompt`` (prompt + committed tokens), so
        preempted requests bucket by their grown effective prompt."""
        if (self._fault_plan is not None
                and self._fault_plan.delays_admission_at(self.decode_calls)):
            return                            # injected admission stall
        if not self.queue:
            return
        free = self._free_slots()
        if not free and (self.eos_id is not None or self._spec
                         or self._was_spec):
            # an EOS — or, with speculation, a multi-token burst through the
            # budget — may have freed a slot we haven't observed yet; _sync
            # keeps the finished requests queued for the next drain()
            self._sync()
            free = self._free_slots()
        if not free and self.preempt_after is not None:
            victims = [s for s in range(self.slots)
                       if self._slot_req[s] is not None
                       and self._slot_ticks[s] >= self.preempt_after]
            if victims:
                # never preempt more slots than there are waiters
                self._preempt(victims[:len(self.queue)])
                free = self._free_slots()
        while self.queue and free:
            head = self.queue[0]
            if len(head.admit_prompt) > self._bucket_cap:
                # sliding-window ring overflow: padded per-row ring alignment
                # is undefined, so this prompt takes the exact solo path
                req = self.queue.pop(0)
                with TraceAnnotation("serve.admit",
                                     bucket=len(req.admit_prompt), rows=1):
                    self._admit_solo(free.pop(0), req)
                continue
            bucket = self._bucket_len(len(head.admit_prompt))
            batch: List[Request] = []
            rest: List[Request] = []
            for r in self.queue:
                if (len(batch) < len(free)
                        and len(r.admit_prompt) <= self._bucket_cap
                        and self._bucket_len(len(r.admit_prompt)) == bucket):
                    batch.append(r)
                else:
                    rest.append(r)
            self.queue = rest
            slot_ids = [free.pop(0) for _ in batch]
            with TraceAnnotation("serve.admit", bucket=bucket,
                                 rows=len(batch)):
                self._admit_batch(slot_ids, batch, bucket)

    # --- slot release + resilience helpers ----------------------------------

    def _finish(self, req: Request, status: str):
        """Terminal bookkeeping shared by every way a request ends."""
        req.status = status
        req.done = True
        req.finish_time = time.perf_counter()
        self._log_event({"e": "finish", "uid": req.uid, "status": status,
                         "n_out": len(req.out)})
        self._finished.append(req)

    def _pad_slots(self, slot_list: List[int]) -> jnp.ndarray:
        """Slot indices padded to a fixed (slots,) shape with the OOB
        sentinel (dropped by every scatter) — varying release counts never
        retrace."""
        idx = np.full((self.slots,), self.slots, np.int32)
        idx[:len(slot_list)] = slot_list
        return jnp.asarray(idx)

    def _deactivate(self, slot_list: List[int]):
        self._active = self._active.at[self._pad_slots(slot_list)].set(
            False, mode="drop")

    def _free_rows(self, slot_list: List[int]):
        """Zero the cache rows of released slots (and the drafter's) back
        to the freshly-allocated state — stale KV/SSM state (or NaN
        contamination) never leaks into the slot's next tenant."""
        idx = self._pad_slots(slot_list)
        self.cache = self._free_fn(self.cache, idx)
        if self._spec:
            self.draft_cache = self._free_draft_fn(self.draft_cache, idx)

    def _release_slot(self, s: int):
        self._slot_req[s] = None
        self._ticks_left[s] = 0
        self._slot_ticks[s] = 0

    def _preempt(self, victims: List[int]):
        """Preempt ``victims``: sync so every committed token is
        attributed, snapshot prompt+out host-side, requeue at the BACK of
        the queue (waiters at the front get the freed slots), and zero the
        device rows. The request re-enters through the normal bucketed
        prefill with its committed tokens folded into the prompt — at T=0
        greedy the continuation is token-identical to the run it left."""
        self._sync()
        live: List[int] = []
        for s in victims:
            req = self._slot_req[s]
            if req is None or req.done:       # sync finished it already
                continue
            live.append(s)
            req.preemptions += 1
            self.preempt_count += 1
            self._release_slot(s)
            self.queue.append(req)
        if live:
            self._deactivate(live)
            self._free_rows(live)

    def _expire_deadlines(self):
        """Cancel every request past its deadline: queued requests are
        dropped before ever holding a slot; resident requests are synced
        first (their partial output is attributed and returned), then
        cancelled mid-stream — device row deactivated and zeroed."""
        now = self.decode_calls
        q_exp = [r for r in self.queue
                 if r.deadline_at is not None and now >= r.deadline_at]
        s_exp = [s for s in range(self.slots)
                 if (r := self._slot_req[s]) is not None
                 and r.deadline_at is not None and now >= r.deadline_at]
        if not q_exp and not s_exp:
            return
        self._sync()          # attribute partial output before cancelling
        for r in q_exp:
            self.queue.remove(r)
            self.deadline_miss_count += 1
            self._finish(r, "deadline")
        cancelled: List[int] = []
        for s in s_exp:
            r = self._slot_req[s]
            if r is None or r.done:           # sync finished/freed it
                continue
            cancelled.append(s)
            self.deadline_miss_count += 1
            self._finish(r, "deadline")
            self._release_slot(s)
        if cancelled:
            self._deactivate(cancelled)
            self._free_rows(cancelled)

    def _poison_for_tick(self) -> jnp.ndarray:
        """The tick's logit-bias vector: the cached all-zeros array in
        healthy operation (same buffer every tick — no retrace, one add in
        the graph), NaN entries for slots the fault plan poisons now."""
        fp = self._fault_plan
        if fp is not None:
            bad = [s for s in fp.nan_slots_at(self.decode_calls)
                   if s < self.slots]
            if bad:
                v = np.zeros((self.slots,), np.float32)
                v[bad] = np.nan
                return jnp.asarray(v)
        return self._poison0

    def _diagnostics(self) -> Dict[str, Any]:
        """The watchdog's dump: what is queued, who holds which slot and
        for how much longer, every counter and the fallback events."""
        return {
            "queue_depth": len(self.queue),
            "queued_uids": [r.uid for r in self.queue],
            "active_slots": [s for s in range(self.slots)
                             if self._slot_req[s] is not None],
            "slots": [{"slot": s, "uid": r.uid,
                       "ticks_left": self._ticks_left[s],
                       "held_ticks": self._slot_ticks[s]}
                      for s in range(self.slots)
                      if (r := self._slot_req[s]) is not None],
            **self.counters(),
            "fallback_events": list(self.fallback_events),
        }

    def _admit_batch(self, slot_ids: List[int], reqs: List[Request],
                     bucket: int):
        """Prefill ``reqs`` (all in one length bucket) right-padded to
        ``bucket`` in a single jitted call, then scatter them into
        ``slot_ids`` with a single jitted admit. The batch dimension is
        pinned to ``slots`` (dummy rows carry an out-of-range slot-map
        entry, so every scatter drops them): jit re-traces are keyed only
        on the bucket length."""
        n = self.slots
        toks = np.zeros((n, bucket), np.int32)
        lens = np.ones((n,), np.int32)            # dummy rows: valid length 1
        slot_map = np.full((n,), self.slots, np.int32)   # OOB -> dropped
        budgets = np.ones((n,), np.int32)
        for i, (s, r) in enumerate(zip(slot_ids, reqs)):
            ap = r.admit_prompt
            toks[i, :len(ap)] = ap
            lens[i], slot_map[i], budgets[i] = len(ap), s, r.remaining
        logits0, src = self._prefill_fn(self.params, jnp.asarray(toks),
                                        jnp.asarray(lens))
        self.prefill_calls += 1
        self.prefill_positions += n * bucket
        self._key, k = jax.random.split(self._key)
        (self.cache, self._tokens, self._active, self._emitted,
         self._budget) = self._admit_many_fn(
            self.cache, self._tokens, self._active, self._emitted,
            self._budget, jnp.asarray(slot_map), src, logits0,
            jnp.asarray(budgets), k)
        if self._spec:
            # the drafter needs the prompt in ITS cache too (logits unused:
            # the target samples every committed token). Rides the same
            # admission round — prefill_calls counts rounds, not models.
            _, dsrc = self._prefill_draft_fn(self.draft_params,
                                             jnp.asarray(toks),
                                             jnp.asarray(lens))
            self.draft_cache = self._admit_draft_many_fn(
                self.draft_cache, jnp.asarray(slot_map), dsrc)
        self._record_admitted(slot_ids, reqs)

    def _admit_solo(self, slot: int, req: Request):
        """Exact-length single-request admission (prompts longer than the
        bucket cap, i.e. past the sliding-window ring)."""
        toks = jnp.asarray([req.admit_prompt], jnp.int32)
        logits0, src = self._prefill_fn(self.params, toks)
        self.prefill_calls += 1
        self.prefill_positions += toks.shape[1]
        self._key, k = jax.random.split(self._key)
        (self.cache, self._tokens, self._active, self._emitted,
         self._budget) = self._admit_fn(
            self.params, self.cache, self._tokens, self._active,
            self._emitted, self._budget, jnp.asarray(slot, jnp.int32),
            src, logits0, jnp.asarray(req.remaining, jnp.int32), k)
        if self._spec:
            _, dsrc = self._prefill_draft_fn(self.draft_params, toks)
            self.draft_cache = self._admit_draft_fn(
                self.draft_cache, jnp.asarray(slot, jnp.int32), dsrc)
        self._record_admitted([slot], [req])

    def _record_admitted(self, slot_ids: List[int], reqs: List[Request]):
        """Post-admit bookkeeping shared by the batched and solo paths:
        record the prefill tokens — emitted by the admitted slots only, done
        iff a request never became active (max_new == 1 / instant EOS) —
        and release slots whose lifetime is already over (drain finishes
        them)."""
        self.admitted += len(reqs)
        self.prefill_tokens += sum(len(r.admit_prompt) for r in reqs)
        self._log_event({"e": "admit", "uids": [r.uid for r in reqs],
                         "slots": list(slot_ids)})
        mask_np = np.zeros((self.slots,), bool)
        for s, r in zip(slot_ids, reqs):
            self._slot_req[s] = r
            self._ticks_left[s] = r.remaining - 1
            self._slot_ticks[s] = 0
            mask_np[s] = True
        mask = jnp.asarray(mask_np)
        self._pending.append((self._tokens, mask, mask & ~self._active,
                              tuple(self._slot_req), None, "admit", None))
        for s in slot_ids:
            if self._ticks_left[s] <= 0:
                self._slot_req[s] = None

    def step(self):
        """Expire deadlines, admit, then advance ALL active slots with ONE
        jitted decode call (speculative mode: up to spec_k+1 tokens per
        slot, still one call). A failed tick call is retried down the
        degradation ladder (spec -> plain, kernel -> fallback) before the
        failure propagates.

        Asynchronous: emitted tokens stay on device until ``drain()``.

        Durability hooks ride the tick boundary: an injected
        ``crash_at_tick`` raises :class:`~repro.serving.resilience.
        InjectedCrash` FIRST (before anything else — a killed process does
        nothing else, and the degradation ladder never sees it), injected
        ``flip_bits`` corrupt the resident weight store, the integrity
        probe then gets its chance to detect + heal, and a completed tick
        lands a periodic snapshot (``snapshot_every``).
        """
        fp = self._fault_plan
        if (fp is not None and fp.crashes_at(self.decode_calls)
                and self.decode_calls not in self._crashed_ticks):
            self._crashed_ticks.add(self.decode_calls)
            raise resilience.InjectedCrash(
                f"injected process kill at decode tick {self.decode_calls}")
        if fp is not None and self.decode_calls not in self._flipped_ticks:
            flips = fp.flips_at(self.decode_calls)
            if flips:
                self._flipped_ticks.add(self.decode_calls)
                for path, bit in flips:
                    self._flip_bit(path, bit)
        if (self._probe_paths is not None
                and self.decode_calls >= self._next_probe):
            self._next_probe = self.decode_calls + self.integrity_every
            self._integrity_probe()
        self._expire_deadlines()
        self._spin_up()
        if not self._occupied():
            return
        emitted_mask = self._active                  # who emits this tick
        owners = tuple(self._slot_req)
        poison = self._poison_for_tick()
        self.live_slot_ticks += sum(r is not None for r in owners)
        with TraceAnnotation("serve.tick"):
            self._key, k = jax.random.split(self._key)
            self._dispatch_tick(owners, emitted_mask, poison, k)
        self.decode_calls += 1
        for s in range(self.slots):
            if self._slot_req[s] is not None:
                self._slot_ticks[s] += 1
                self._ticks_left[s] -= 1
                if self._ticks_left[s] <= 0:
                    self._release_slot(s)        # budget exhausted this tick
        if (self.snapshot_dir is not None and self.snapshot_every is not None
                and self.decode_calls % self.snapshot_every == 0
                and self.decode_calls != self._last_snapshot_tick):
            self.snapshot()

    def _call_tick(self, poison, k):
        """One jitted tick on the CURRENT graph (spec or plain), with the
        fault plan's injected failures raised IN PLACE of the call — before
        it, so donated buffers are intact and a ladder retry sees
        consistent state. Each planned failure fires once."""
        fp = self._fault_plan
        if (fp is not None and fp.fails_at(self.decode_calls)
                and self.decode_calls not in self._failed_ticks):
            self._failed_ticks.add(self.decode_calls)
            raise resilience.InjectedFault(
                f"injected tick failure at decode tick {self.decode_calls}")
        if self._spec:
            return self._tick_fn(
                self.params, self.draft_params, self.cache, self.draft_cache,
                self._tokens, self._active, self._emitted, self._budget,
                poison, k)
        return self._tick_fn(self.params, self.cache, self._tokens,
                             self._active, self._emitted, self._budget,
                             poison, k)

    def _dispatch_tick(self, owners, emitted_mask, poison, k):
        """Run one tick, walking the degradation ladder on failure: each
        retry first applies ``resilience.degrade_step`` (spec -> plain,
        then kernel -> fallback graphs); with the ladder exhausted, an
        injected (transient) fault still earns one same-graph retry, and
        anything else propagates."""
        attempts = 0
        while True:
            spec_call = self._spec
            try:
                out = self._call_tick(poison, k)
                break
            except Exception as e:
                attempts += 1
                label = resilience.degrade_step(self) if self.degrade else None
                if (label is None and attempts < 3
                        and isinstance(e, resilience.InjectedFault)):
                    label = "retry"
                if label is None or attempts >= 4:
                    raise
                self.fallback_events.append((self.decode_calls, label))
        if spec_call:
            (self.cache, self.draft_cache, self._tokens, self._active,
             self._emitted, done, out_toks, counts, accepted, bad) = out
            self._pending.append((out_toks, counts, done, owners, accepted,
                                  "tick", bad))
        else:
            (self.cache, self._tokens, self._active, self._emitted,
             done, bad) = out
            self._pending.append((self._tokens, emitted_mask, done, owners,
                                  None, "tick", bad))

    def _sync(self):
        """Bulk-sync everything emitted since the last sync; attribute
        tokens to requests via per-tick owner snapshots. Newly finished
        requests accumulate in ``_finished`` until ``drain()`` hands them
        out (an internal sync must never lose them).

        Records carry variable per-slot token counts (speculative ticks emit
        1..spec_k+1 tokens per slot); ONE ``device_get`` moves every pending
        array to the host, so the async no-per-token-sync property holds in
        both modes. Per-request tick/accept-histogram stats and the engine's
        ``spec_drafted``/``spec_accepted`` counters are folded in here."""
        if not self._pending:
            return
        with TraceAnnotation("serve.sync.wait"):
            moved = jax.device_get([(toks, counts, done,
                                     () if acc is None else acc,
                                     () if bad is None else bad)
                                    for toks, counts, done, _, acc, _, bad
                                    in self._pending])
        with TraceAnnotation("serve.sync.host"):
            self._attribute(moved)

    def _attribute(self, moved):
        """The host half of ``_sync``: give the moved tokens to their
        requests, finish and quarantine, journal the commits."""
        quarantined: List[int] = []
        committed: Dict[int, int] = {}        # uid -> tokens attributed now
        for (toks, counts, done, acc, bad), (_, _, _, owners, _, kind, _) \
                in zip(moved, self._pending):
            badv = None if isinstance(bad, tuple) else np.asarray(bad)
            for s in np.nonzero(counts)[0]:
                if badv is not None and badv[s]:
                    continue       # poisoned row: frozen in-graph, no tokens
                req = owners[s]
                if req is not None:
                    n = int(counts[s])
                    req.out.extend(int(x) for x in toks[s, :n])
                    committed[req.uid] = committed.get(req.uid, 0) + n
                    if kind == "tick":
                        req.ticks += 1
                        req.accept_hist[n] = req.accept_hist.get(n, 0) + 1
            if not isinstance(acc, tuple):            # speculative tick
                live = np.asarray(counts) > 0
                # k from the record's window width: still right for records
                # drained after a mid-run spec->plain degrade
                self.spec_drafted += int((toks.shape[1] - 1) * live.sum())
                self.spec_accepted += int(np.asarray(acc)[live].sum())
            for s in np.nonzero(done)[0]:
                req = owners[s]
                if req is not None and not req.done:
                    self._finish(req, "ok")
                    if self._slot_req[s] is req:   # early EOS: free the slot
                        self._release_slot(s)
            if badv is not None:
                for s in np.nonzero(badv)[0]:
                    req = owners[s]
                    if req is not None and not req.done:
                        self.poisoned_count += 1
                        self._finish(req, "poisoned")
                        if self._slot_req[s] is req:
                            self._release_slot(s)
                            quarantined.append(s)
        self._pending.clear()
        if self._journal is not None:
            for uid in sorted(committed):
                self._log_event({"e": "commit", "uid": uid,
                                 "n": committed[uid]})
        if quarantined:
            # the tick already deactivated poisoned rows on-device; zeroing
            # them keeps contaminated state out of the slot's next tenant
            self._free_rows(sorted(set(quarantined)))

    def drain(self) -> List[Request]:
        """Sync pending emissions and return every request that finished
        since the last ``drain()`` call."""
        self._sync()
        out, self._finished = self._finished, []
        return out

    def run_all(self, max_ticks: Optional[int] = None) -> List[Request]:
        """Drive until queue and slots are empty.

        ``max_ticks`` (default: the engine's ``max_ticks``; None = no
        watchdog) bounds the number of driver iterations — a wedged engine
        (admission stalled, a slot that never finishes) raises
        :class:`~repro.serving.resilience.WatchdogExpired` carrying a
        diagnostic dump (queue depth, active slots, per-slot tick budgets,
        every resilience counter) instead of spinning forever. Requests
        already finished stay drainable after the raise."""
        if max_ticks is None:
            max_ticks = self.max_ticks
        done: List[Request] = []
        iters = 0
        while self.queue or self._occupied():
            if max_ticks is not None and iters >= max_ticks:
                self._sync()
                # hand the already-finished work back through drain()
                self._finished = done + self._finished
                diag = self._diagnostics()
                raise WatchdogExpired(
                    f"run_all exceeded max_ticks={max_ticks} with work "
                    f"still pending: queue depth {diag['queue_depth']}, "
                    f"active slots {diag['active_slots']}, per-slot state "
                    f"{diag['slots']}", diag)
            self.step()
            iters += 1
            # periodic drain bounds the pending-buffer growth (one record
            # per tick) and, with EOS, discovers freed slots early
            if self.decode_calls % self.drain_every == 0:
                done.extend(self.drain())
        done.extend(self.drain())
        return done
