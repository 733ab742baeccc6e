"""Continuous-batching serving scheduler with overlap admission.

Fixed-slot continuous batching (vLLM-style, static shapes for XLA): the
engine keeps `n_slots` decode lanes and admits a new prompt into ANY free
lane on ANY step.  Admission prefills the prompt against a throwaway
1-lane dense cache and splices it into the live cache through a pluggable
KV-cache backend (serving/kv_cache.py):

  * cache_backend="dense" — today's worst-case (L, n_slots, Smax, Kv, D)
    layout; the equivalence baseline.
  * cache_backend="paged" — fixed-size pages + per-lane page table + host
    free-list allocator; lanes allocate pages as `pos` grows and return
    them on retirement, so short requests stop paying Smax memory
    (benchmarks/bench_paged_cache.py measures the resident-bytes drop).

Per-slot position counters stay honest (the decode step takes a per-lane
position vector), retirement is per-slot on EOS-after-emit / max_new /
max_seq, and retired lanes are masked out of sampling.  Sampling runs
INSIDE the jitted decode step: per-lane temperature / nucleus top-p with
a per-(step, lane) PRNG key, falling back to greedy argmax for
temperature=0 lanes, so decode stays a single device dispatch.

Prompt lengths are bucketed (DEFAULT_BUCKETS, capped at `prompt_bucket`)
so admission compiles one prefill per bucket — a small fixed set of
shapes; the decode step compiles exactly once.  Prompts longer than the
largest bucket keep only their last `bucket` tokens; the request is
flagged `truncated=True` and the engine warns once.

This is the single-host engine; at pod scale the same slot logic runs
per data-parallel replica group with the model sharded over 'model'
(the decode step is already the dry-run-verified sharded function).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import exempt, owned_by, runs_on
from repro.kernels import paged_attention
from repro.models import api
from repro.models.attention import _use_paged_kernel
from repro.serving import dsg_runtime, kv_cache, telemetry
from repro.serving.kv_cache import CacheHandle

DEFAULT_BUCKETS = (16, 32, 64, 96, 128, 192, 256, 384, 512)


def bucket_sizes(prompt_bucket: int, max_seq: int,
                 buckets: Optional[Sequence[int]] = None) -> tuple:
    """The prompt buckets an engine will compile: candidate sizes capped
    at prompt_bucket and at max_seq - 1 (a prompt filling every cache
    position would leave no decode headroom).  Exposed so pool-sizing
    code (benchmarks/bench_paged_cache.py) derives the same largest
    bucket as the engine's admission path."""
    cap = min(prompt_bucket, max_seq - 1)
    bs = buckets if buckets is not None else DEFAULT_BUCKETS
    return tuple(sorted({min(b, cap) for b in bs}))

def params_device(params):
    """The one device every params leaf lives on; None (the process
    default) when the params are host arrays or span several devices."""
    devs = {d for leaf in jax.tree.leaves(params)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}
    return devs.pop() if len(devs) == 1 else None


_ADMIT_SALT = 0xADA117   # folds admission PRNG keys off the decode stream

#: Terminal request states.  "ok" is stamped at retirement; "failed" and
#: "timed_out" are stamped by the Router's fault-tolerance layer
#: (serving/router.py) — an engine on its own never fails a request.
REQUEST_STATUSES = ("pending", "ok", "failed", "timed_out")


class EngineAborted(RuntimeError):
    """Raised by an engine whose `abort` flag was set: the stall-timeout
    containment path (serving/router.py) cannot kill a thread stuck
    inside a device call, so it asks the engine to abandon its in-flight
    state at the NEXT step boundary — the raise funnels the replica into
    the standard failure/reclaim path."""


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (P,) int32
    max_new: int = 32
    eos_id: Optional[int] = None
    temperature: float = 0.0         # 0 -> greedy argmax
    top_p: float = 1.0               # nucleus mass kept when sampling
    deadline_s: Optional[float] = None   # max submit->finish wait (router)
    # filled by the engine (time.perf_counter() stamps — monotonic, for
    # duration math only; NTP steps would corrupt wall-clock latencies):
    output: List[int] = field(default_factory=list)
    truncated: bool = False          # prompt exceeded the largest bucket
    submitted: float = 0.0
    started: float = 0.0             # admission time (first compute)
    first_token: float = 0.0         # first output token observed (TTFT)
    finished: float = 0.0
    status: str = "pending"          # one of REQUEST_STATUSES
    retries: int = 0                 # failover re-dispatches consumed


@dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0                      # next write position in the cache

    @property
    def free(self) -> bool:
        return self.req is None


@dataclass
class StepPlan:
    """Host-built operands for one jitted decode dispatch.

    `ServingEngine.begin_step()` runs the host half of a decode step
    (admission, emit bookkeeping, page-table growth) and returns a plan;
    the device half dispatches the jitted decode with the plan's operands
    and `commit_step()` records the result (retirement, counters).  The
    split exists so replica executors (serving/parallel_exec.py) can
    batch the device half across engines — the sharded executor stacks
    the operands of several plans along a leading replica axis and runs
    one vmapped decode.  `ServingEngine.step()` builds its plans the same
    way but keeps one decode in flight: it dispatches the next plan
    before it commits the last.
    """
    active: List[int]                 # slot indices decoding this step
    donor: int                        # active lane free lanes mirror
    # (n_slots,) int32 decode inputs; -1 where the lane takes the device
    # token of the step still in flight (an `ahead` plan)
    tok: np.ndarray
    pos: np.ndarray                   # (n_slots,) int32 write positions
    free_mask: np.ndarray             # (n_slots,) bool
    temps: np.ndarray                 # (n_slots,) float32
    top_ps: np.ndarray                # (n_slots,) float32
    sample: bool                      # any lane with temperature > 0
    # fused-chunk dispatch (decode_chunk > 1): `chunk` micro-steps run in
    # one device dispatch, with per-lane EOS / emit-budget freezing on
    # device, so begin_step emits nothing and commit_chunk lags a full
    # chunk behind.  eos_ids uses -1 for "no stop token".
    chunk: int = 1
    eos_ids: Optional[np.ndarray] = None   # (n_slots,) int32
    emit_left: Optional[np.ndarray] = None  # (n_slots,) int32 budget
    refresh: bool = False             # DSG: collect the refresh scores
    bound: int = 0                    # DSG: static CSR row width
    admits: int = 0                   # prompts admitted by this begin_step
    # dispatched before the host read the previous step's tokens: its
    # lanes decode those tokens straight from the device (ServingEngine.
    # step); `then` is set on the previous plan, whose commit appends them
    ahead: bool = False
    then: Optional["StepPlan"] = None


class _InFlight(NamedTuple):
    """A decode dispatched and not yet read (ServingEngine.step)."""
    plan: StepPlan
    outs: list                        # device outputs but cache and scores
    scores: object                    # a DSG engine's refresh scores or None
    seconds: float                    # the dispatch's host time


def _restore_table(data, c):
    # the host mirror is the source of truth for the page table;
    # the lane-mirrored view must not escape the step
    if c.kind != "paged":
        return data
    return {**data, "page_table": c.data["page_table"]}


def _micro_step(cfg, p, d, tok, c, pos, free, donor, csr, collect,
                sampling):
    """One decode step over every lane: the body each decode program is
    built from.  Lanes where `free` holds mirror lane `donor` through the
    cache view (kv_cache.decode_view) and, for a DSG engine, through the
    group-CSR operand `csr` (dsg_runtime.mirror_csr; None without DSG).
    `collect` (python-static) also returns each layer's DRS group scores
    of this step's FFN inputs, which a DSG refresh rewrites due lanes'
    patterns from.  `sampling` is None for greedy argmax (no vocab sort)
    or (key, step, temps, top_ps), keyed per (step, lane).  Returns
    (next tokens (B,), cache, scores or None, a MoE model's counts over
    the non-free lanes (3,) or None — models/moe.py `moe_ffn_dropless`)."""
    view = kv_cache.decode_view(c, free, donor)
    kw = {}
    if csr is not None:
        kw.update(ffn_csr=dsg_runtime.mirror_csr(csr, free, donor),
                  collect_drs_scores=collect)
    if cfg.is_moe:
        kw["moe_count"] = ~free[:, None]
    logits, data, *rest = api.decode_step(p, d, cfg, tok, view, pos, **kw)
    scores = rest.pop(0) if collect else None
    stats = rest.pop(0) if cfg.is_moe else None
    if sampling is None:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        key, step, temps, top_ps = sampling
        keys = jax.random.split(jax.random.fold_in(key, step), tok.shape[0])
        nxt = sample_tokens(logits, keys, temps, top_ps)
    return (nxt, CacheHandle(_restore_table(data, c), c.kind, c.page_size),
            scores, stats)


def _one_step(cfg, p, d, ops, sample, refresh):
    """A single-token decode program: ops = (tok (B,), cache, pos,
    free_mask, donor, prev, csr[, key, step, temps, top_ps]).  A lane
    decodes its host token where `tok` >= 0, else its entry of `prev`:
    the next tokens of the decode dispatched before this one, still on
    the device (an ahead plan; ServingEngine.step).  Free lanes then
    mirror the donor's token; the host has mirrored their positions.
    Returns (next tokens (B,), cache, scores or None); a MoE model's
    counts follow the tokens, (B + 3,), so that they reach the host in
    the tokens' transfer, and `commit_step` splits them."""
    tok, c, pos, free_mask, donor, prev, csr, *sampling = ops
    tok = jnp.where(tok >= 0, tok, prev[:tok.shape[0]])
    tok = jnp.where(free_mask, tok[donor], tok)
    nxt, c, scores, stats = _micro_step(cfg, p, d, tok[:, None], c, pos,
                                        free_mask, donor, csr, refresh,
                                        sampling if sample else None)
    if stats is not None:
        nxt = jnp.concatenate([nxt, stats])
    return nxt, c, scores


def _chunk_steps(cfg, p, d, ops, sample, refresh, chunk, max_seq):
    """A fused decode-chunk program: `chunk` micro-steps scanned in one
    dispatch, so the per-token host sync is paid once per chunk.  ops =
    (tok (B,), cache, pos, done, emit_left, eos_ids, csr[, key, step0,
    temps, top_ps]); eos_ids uses -1 for "no stop token".

    The scan carry keeps (tok, pos, done, emit_left, cache, MoE counts)
    on device.  Per micro-step, lanes whose done bit is set (initially
    the free lanes; later any lane that hit EOS / its max_new budget /
    max_seq) mirror the first live lane exactly like the chunk=1 donor
    path — `jnp.argmin(done)` re-picks the donor every micro-step
    because the chunk=1 donor (first active lane) can itself finish
    mid-chunk.  A frozen lane's writes are donor duplicates (paged) or
    overwritten at readmission (dense), identical to the chunk=1
    free-lane contract.  A MoE model's counts are summed over the chunk.

    The CSR operand is constant across the chunk (the engine enforces
    refresh_interval % chunk == 0, and lanes admit at chunk boundaries,
    so a refresh-due point can only land on the last micro-step), so
    with `refresh` the last micro-step runs outside the scan and
    returns its DRS scores.

    Returns `blk` (chunk, B) int32 — the token each lane emitted at each
    micro-step (its decode INPUT, matching begin_step's emit-before-
    decode order at chunk=1) — `flags` (chunk, B) bool marking which
    entries are real (a lane's flag column is a monotone prefix: done
    never unsets, so the host takes `blk[:n, i]`), each lane's pending
    next-step token (MoE counts following, as in _one_step), the cache
    and the scores or None.

    The sampled program folds the key schedule as (seed, step0 + k,
    lane) — bitwise the per-step schedule, so a sampled lane's stream
    is invariant to the chunk size AS LONG AS its admission step and
    `_draws` count match (chunked scheduling admits at chunk boundaries,
    which shifts admission timing under load; temperature-0 streams are
    unconditionally chunk-invariant)."""
    tok, c, pos, done, emit_left, eos_ids, csr, *sampling = ops
    key, step0, temps, top_ps = sampling if sample else (None, 0, None,
                                                         None)

    def micro(carry, k, collect):
        tok, pos, done, left, c, stats = carry
        donor = jnp.argmin(done)          # first live lane (False < True)
        tok_in = jnp.where(done, tok[donor], tok)
        pos_in = jnp.where(done, pos[donor], pos)
        nxt, c, scores, st = _micro_step(
            cfg, p, d, tok_in[:, None], c, pos_in, done, donor, csr,
            collect, (key, k, temps, top_ps) if sample else None)
        live = ~done
        fin = live & (((eos_ids >= 0) & (tok_in == eos_ids))
                      | (left <= 1) | (pos_in + 1 >= max_seq))
        carry = (jnp.where(live, nxt, tok),
                 jnp.where(live, pos_in + 1, pos),
                 done | fin,
                 jnp.where(live, left - 1, left), c,
                 None if st is None else stats + st)
        return carry, (tok, live), scores

    n = chunk - 1 if refresh else chunk
    xs = step0 + jnp.arange(n) if sample else None
    carry = (tok, pos, done, emit_left, c,
             jnp.zeros(3, jnp.int32) if cfg.is_moe else None)
    carry, (blk, flags) = jax.lax.scan(
        lambda cr, k: micro(cr, k, False)[:2], carry, xs, length=n)
    scores = None
    if refresh:
        carry, (tok_l, live_l), scores = micro(carry, step0 + n, True)
        blk = jnp.concatenate([blk, tok_l[None]], axis=0)
        flags = jnp.concatenate([flags, live_l[None]], axis=0)
    tok_f, _, _, _, c, stats = carry
    if stats is not None:
        tok_f = jnp.concatenate([tok_f, stats])
    return blk, flags, tok_f, c, scores


def make_decode_fns(cfg, *, dsg: bool = False, chunk: int = 1,
                    max_seq: int = 0):
    """Build the (greedy, sample) decode callables the engine jits, both
    from one step body (`_micro_step`): a single-token step, or with
    `chunk` > 1 the fused chunk that scans it.  Each is called as
    fn(params, dsg, *ops, refresh=False) with the operands
    `ServingEngine.decode_operands` builds; `refresh` (static) collects
    a DSG engine's refresh scores.  Outputs end with (cache, scores or
    None).

    Module-level (rather than closures in `ServingEngine.__init__`) so
    the sharded replica executor can vmap THE SAME callables over a
    leading replica axis.  The names are the programs' names in a
    device trace: `_decode_greedy` / `_decode_sample`, `_dsg_greedy` /
    `_dsg_sample` for a DSG engine's (chipbench's decode_step_ms reads
    them), `_chunk_greedy` / `_chunk_sample` for a fused chunk's."""
    kind = "chunk" if chunk > 1 else "dsg" if dsg else "decode"

    def step(p, d, ops, sample, refresh):
        if chunk > 1:
            return _chunk_steps(cfg, p, d, ops, sample, refresh, chunk,
                                max_seq)
        return _one_step(cfg, p, d, ops, sample, refresh)

    def greedy(p, d, *ops, refresh=False):
        return step(p, d, ops, False, refresh)

    def sample(p, d, *ops, refresh=False):
        return step(p, d, ops, True, refresh)

    greedy.__name__ = greedy.__qualname__ = f"_{kind}_greedy"
    sample.__name__ = sample.__qualname__ = f"_{kind}_sample"
    return greedy, sample


def sample_tokens(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                  top_ps: jax.Array) -> jax.Array:
    """Per-lane temperature + nucleus sampling, jit-friendly.

    logits (B, V), keys (B, 2) per-lane PRNG keys, temps/top_ps (B,).
    Lanes with temperature 0 take the argmax; the rest sample from the
    smallest prefix of the sorted distribution whose mass reaches top_p
    (the crossing token is kept, so top-1 always survives).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_ps[:, None]
    keep = keep.at[:, 0].set(True)     # top-1 survives even top_p == 0
    kth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    lg = jnp.where(lg >= kth, lg, -jnp.inf)
    samp = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
    return jnp.where(temps > 0, samp, greedy)


@owned_by("worker", "queue", "done", "slots", "cache", "steps",
          "decode_seconds", "decode_tokens", "_next_tok", "_draws",
          "_warned_truncation", "_prefill_cache", "prefill_cache_hits",
          "_inflight", "_tok_dev")
class ServingEngine:
    """Continuous batching over a fixed slot count.

    Static-shape discipline: a prompt is right-aligned into the smallest
    length bucket that holds it (shorter prompts left-padded), so there is
    one prefill computation per bucket and ONE decode computation to
    compile.  Each admission runs a 1-lane prefill and splices the result
    into the live batched cache via the backend — active lanes' K/V bytes
    are never touched, and under per-row DRS selection
    (threshold_mode="topk") their outputs are bit-identical to a solo run
    AND across cache backends (see tests/test_serving_overlap.py).  With
    the paper's inter-sample threshold sharing (threshold_mode="shared")
    all lanes couple to batch row 0's scores by design; the engine keeps
    that row meaningful by mirroring idle lanes onto an active one.

    The paged backend reserves a request's worst-case page count
    (min(bucket + max_new, max_seq)) at admission, so page-table growth
    during decode can never run out; a pool with too few free pages defers
    admission until retirements return pages.
    """

    def __init__(self, cfg, params, dsg, *, n_slots: int = 4,
                 max_seq: int = 256, prompt_bucket: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 cache_backend: Union[str, object] = "dense",
                 page_size: int = 16, cache_tokens: Optional[int] = None,
                 seed: int = 0, dsg_serving=None, decode_chunk: int = 1,
                 prefix_sharing: bool = False):
        if decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1 (got {decode_chunk})")
        self.decode_chunk = decode_chunk
        self.cfg = cfg
        self.params = params
        self.dsg = dsg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.page_size = page_size
        # a prompt filling all max_seq positions would admit a lane with
        # zero decode headroom (its first decode write lands out of cache
        # range), so the largest bucket always leaves one position free
        self.prompt_bucket = min(prompt_bucket, max_seq - 1)
        self.buckets = bucket_sizes(prompt_bucket, max_seq, buckets)
        self.queue: collections.deque = collections.deque()
        self.slots = [_Slot() for _ in range(n_slots)]
        self.done: Dict[int, Request] = {}
        self.steps = 0
        self.decode_seconds = 0.0     # time inside jitted decode steps
        self.decode_tokens = 0        # tokens emitted by those steps
        self._draws = 0               # admission PRNG counter
        self._warned_truncation = False
        self._base_key = jax.random.PRNGKey(seed)
        # fault-tolerance surface (serving/router.py, runtime/
        # fault_tolerance.py).  `abort` is a benign cross-thread flag: the
        # router sets it (stall-timeout containment) and the engine's own
        # worker observes it at the next step boundary — a plain bool
        # store/load under the GIL, never read-modify-written.
        self.replica_index = 0        # set by the Router (attribution)
        self.fault_injector = None    # ServingFaultInjector (chaos runs)
        self.abort = False
        # spans and counters of this engine's host work
        # (serving/telemetry.py; docs/serving.md, "Spans and counters")
        self.telemetry = telemetry.Telemetry()

        self.backend = (cache_backend if hasattr(cache_backend, "make")
                        else kv_cache.get_backend(
                            cache_backend, page_size=page_size,
                            total_tokens=cache_tokens,
                            prefix_sharing=prefix_sharing))
        self.backend.telemetry = self.telemetry
        # copy-on-write shared-prefix reuse (docs/cache_backends.md):
        # admission hashes the bucketed prompt row into a prefix chain,
        # maps already-resident pages by refcount bump, and — when EVERY
        # prompt page is shared — replays the cached prefill outputs
        # instead of recomputing the prompt (zero prefill FLOPs).
        self.prefix_sharing = bool(prefix_sharing)
        if self.prefix_sharing and not getattr(self.backend,
                                               "prefix_sharing", False):
            raise ValueError(
                "prefix_sharing=True needs a PagedBackend built with "
                "prefix_sharing enabled (cache_backend='paged', or pass "
                "a PagedBackend(prefix_sharing=True) instance)")
        # LRU of full-prompt prefill outputs keyed by the chain's last
        # digest: (last-token logits, DRS scores or None).  Bounded so a
        # long-lived engine's host memory stays flat; entries are tiny
        # ((vocab,) logits) next to the KV pool.
        self._prefill_cache: collections.OrderedDict = \
            collections.OrderedDict()
        self._prefill_cache_cap = 128
        self.prefill_cache_hits = 0
        # the KV cache and the prefill template are created on the device
        # that holds the params (a threaded Router replica's own device),
        # not on the process default and moved later
        with jax.default_device(params_device(params)):
            self.cache = self.backend.make(cfg, n_slots, max_seq)
            # zero 1-lane dense template reused by every admission
            # (prefill is functional: the template is never mutated, and
            # its zero tail wipes any stale K/V when merged over a retired
            # dense lane)
            self._lane0 = api.make_cache(cfg, 1, max_seq)
        # token each lane feeds to its next decode step (sampled from the
        # lane's latest logits; junk for free lanes, masked at emit time)
        self._next_tok = np.zeros(n_slots, np.int32)
        # the same tokens on the device, as the latest decode returned them
        # (a MoE model's counts after them): the `prev` operand of every
        # single-token decode, whose lanes of an ahead plan decode them
        # before the host has read them.  Zeros until the first decode,
        # placed as the cache is, so that every dispatch passes one kind
        # of array and hits one compiled program.
        with jax.default_device(params_device(params)):
            self._tok_dev = jnp.zeros(n_slots + 3 * cfg.is_moe, jnp.int32)
        # the decode dispatched and not yet read (an _InFlight), or None
        self._inflight = None

        # sampling is fused into the jitted decode step (one device
        # dispatch per step) — with a separate greedy-only variant so the
        # common all-temperature-0 step never pays the full-vocab
        # sort/softmax of nucleus sampling.  Admission is three
        # dispatches (prefill, backend splice, first-token pick); it runs
        # once per request, not per step.
        def _prefill(p, d, toks, lane0):
            logits, lane = api.prefill(p, d, cfg, {"tokens": toks}, lane0)
            return logits[0], lane

        def _prefill_moe(p, d, toks, lane0, n_prompt):
            # the greedy first token and the expert-layer counts over the
            # true prompt tokens (the row's last n_prompt), in one array:
            # the admission's one read of the device
            count = jnp.arange(toks.shape[1]) >= toks.shape[1] - n_prompt
            logits, lane, stats = api.prefill(p, d, cfg, {"tokens": toks},
                                              lane0, moe_count=count[None])
            first = jnp.argmax(logits[0]).astype(jnp.int32)
            return logits[0], lane, jnp.concatenate([first[None], stats])

        def _first_tok(logits, key, draw, temp, top_p):
            k = jax.random.fold_in(jax.random.fold_in(key, _ADMIT_SALT),
                                   draw)
            return sample_tokens(logits[None], jax.random.split(k, 1),
                                 temp[None], top_p[None])[0]

        self._jit_prefill = jax.jit(_prefill)
        self._jit_prefill_moe = jax.jit(_prefill_moe) if cfg.is_moe else None
        self._jit_first = jax.jit(_first_tok)

        # DSG serving runtime (serving/dsg_runtime.py): per-lane group-CSR
        # patterns feed a sparse FFN decode; refresh scores ride back out
        # of the refresh variant of the decode step
        scfg = dsg_runtime.as_serving_config(dsg_serving)
        self.dsg_rt = None
        if scfg is not None:
            if dsg is None or not cfg.dsg.enabled:
                raise ValueError(
                    "dsg_serving needs DSG state: cfg.dsg.enabled and a "
                    "non-None dsg pytree")
            if cfg.is_moe or cfg.act != "swiglu":
                raise ValueError(
                    "dsg_serving targets the dense SwiGLU FFN family "
                    f"(got act={cfg.act!r}, moe_experts={cfg.moe_experts})")
            if cfg.dsg.score != "relu_sum":
                raise ValueError(
                    "the on-device refresh (kernels/drs_search.drs_scores) "
                    f"computes relu_sum scores; cfg.dsg.score is "
                    f"{cfg.dsg.score!r}")
            if decode_chunk > 1 and scfg.refresh_interval % decode_chunk:
                raise ValueError(
                    f"decode_chunk ({decode_chunk}) must divide the DSG "
                    f"refresh_interval ({scfg.refresh_interval}): refresh "
                    "cadence is per-lane emitted-token count, and a due "
                    "point landing mid-chunk could not rewrite the CSR "
                    "pattern the chunk already dispatched with")
            self.dsg_rt = dsg_runtime.DSGRuntime(cfg, scfg, n_slots,
                                                 self.telemetry)

            def _prefill_dsg(p, d, toks, lane0):
                logits, lane, scores = api.prefill(
                    p, d, cfg, {"tokens": toks}, lane0,
                    collect_drs_scores=True)
                return logits[0], lane, scores

            self._jit_prefill_dsg = jax.jit(_prefill_dsg)

        # the decode programs, indexed by `sample`: the fused chunk's when
        # decode_chunk > 1.  The engine cache handle is donated: the
        # caller always rebinds self.cache to the result, and donation
        # lets XLA update the pools in place instead of copying the whole
        # cache every call.  Static: a DSG engine's `refresh`, and its CSR
        # operand's width is a shape; nothing else varies, so warm_decode
        # compiles every program the engine will dispatch.
        greedy, sample = make_decode_fns(cfg, dsg=self.dsg_rt is not None,
                                         chunk=decode_chunk, max_seq=max_seq)
        self._jit_decode = (
            jax.jit(greedy, donate_argnums=(3,), static_argnames=("refresh",)),
            jax.jit(sample, donate_argnums=(3,), static_argnames=("refresh",)))
        # step() keeps one decode in flight when a step's operands need no
        # host read of the step before it: not under DSG, whose next CSR
        # operand comes from the scores read on the host, nor in a fused
        # chunk, whose budgets and EOS freezes are settled at its commit
        self._pipelined = self.dsg_rt is None and decode_chunk == 1
        self._moe_none = (dict(moe_rows=0, moe_experts_hit=0, moe_rows_max=0)
                          if cfg.is_moe else {})

    # -- public API ---------------------------------------------------------

    @exempt("queue", reason="cross-thread entry point: the dispatching "
            "executor serializes it (ThreadedExecutor.dispatch holds "
            "_cond) or no drive is in flight; deque.append is atomic "
            "under the GIL and the REPRO_TSAN guarded deque still "
            "covers the site")
    def submit(self, req: Request):
        # keep an earlier stamp if one exists: a front-end router stamps
        # submission time at ITS queue, and latency should span the whole
        # wait, not just the slice after dispatch to this replica
        req.submitted = req.submitted or time.perf_counter()
        self.queue.append(req)

    @runs_on("worker")
    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Step until no request is queued or resident, or `max_steps`
        decodes have been dispatched; commits the one in flight."""
        while (self.queue or any(not s.free for s in self.slots)) \
                and self.steps + (self._inflight is not None) < max_steps:
            self.step()
        self._settle()
        return self.done

    def drain(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Run until every queued request is admitted, decoded, and
        retired (no new submissions assumed) — the retirement-draining
        primitive a front-end router calls per replica."""
        return self.run(max_steps=max_steps)

    # -- introspection (read by serving/router.py routing policies) ----------

    def queue_depth(self) -> int:
        """Requests accepted by submit() but not yet admitted to a lane."""
        return len(self.queue)

    def free_slots(self) -> int:
        """Decode lanes currently without a resident request."""
        return sum(s.free for s in self.slots)

    def busy_slots(self) -> int:
        return self.n_slots - self.free_slots()

    def free_pages(self) -> int:
        """Unreserved free pages in the paged backend's BlockAllocator —
        the headroom a router's `least_pages` policy balances on.  Dense
        engines have no allocator; each free lane permanently owns a
        max_seq stripe, reported in equivalent pages of this engine's
        `page_size` so the number stays comparable across backends."""
        if self.cache.kind == "paged":
            return (self.backend.allocator.free_pages
                    - int(self.backend._resv.sum()))
        return self.free_slots() * (self.max_seq // max(self.page_size, 1))

    def _admit_chain(self, req: Request):
        """(prefix chain, prompt bucket) admission would use for `req` —
        None chain when sharing is off.  Exposed to the sharing-aware
        page math below so routing reservations (Router least_pages)
        see the same expected-sharing credit admission will take."""
        pb = self._bucket_for(len(req.prompt))
        if not self.prefix_sharing:
            return None, pb
        toks = np.zeros(pb, np.int32)
        pr = req.prompt[-pb:]
        toks[pb - len(pr):] = pr
        return kv_cache.prefix_chain(toks, self.page_size), pb

    def pages_needed(self, req: Request) -> int:
        """Worst-case page reservation admitting `req` would take (the
        same `min(bucket + max_new, max_seq)` extent _admit reserves).
        With prefix sharing the count credits prompt pages already
        resident (they are mapped, not allocated) and charges the
        partial-tail COW page — so `least_pages` reservations account
        for expected sharing."""
        chain, pb = self._admit_chain(req)
        need = min(pb + req.max_new, self.max_seq)
        if self.cache.kind == "paged":
            pages = self.backend.pages_for(need)
            if chain is not None:
                pages += self.backend.sharing_adjustment(chain, pb)
            return max(pages, 0)
        return -(-need // max(self.page_size, 1))

    def can_admit_request(self, req: Request) -> bool:
        """True when `req`, submitted now with an empty queue ahead of it,
        would be admitted by the next step: a lane is free and the cache
        backend can cover its worst-case reservation (sharing-aware —
        see pages_needed)."""
        chain, pb = self._admit_chain(req)
        need = min(pb + req.max_new, self.max_seq)
        if chain is not None:
            return (self.free_slots() > 0
                    and self.backend.can_admit(need, chain=chain,
                                               prompt_tokens=pb))
        return self.free_slots() > 0 and self.backend.can_admit(need)

    # -- engine internals ---------------------------------------------------

    def _bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        return self.buckets[-1]      # longer prompts truncate to max bucket

    @runs_on("worker")
    def _remember_prefill(self, key: bytes, logits, sc_np) -> None:
        """Cache a full-prompt prefill result (last-token logits + DRS
        scores) under the prompt chain's final digest, LRU-bounded.  The
        entry is only ever REPLAYED when every prompt page is still
        resident, and it reproduces the prefill bitwise: identical
        padded tokens through the same jitted prefill yield identical
        logits, so the first sampled/greedy token — and with it the
        whole stream — matches the recompute path exactly."""
        self._prefill_cache[key] = (logits, sc_np)
        self._prefill_cache.move_to_end(key)
        while len(self._prefill_cache) > self._prefill_cache_cap:
            self._prefill_cache.popitem(last=False)

    @runs_on("worker")
    def _admit(self) -> int:
        """Admit queued prompts into free lanes via backend cache surgery;
        returns how many were admitted.

        Every free lane refills immediately (subject to the paged
        backend having pages for the request's reservation)."""
        admitted = 0
        for i, slot in enumerate(self.slots):
            if not slot.free or not self.queue:
                continue
            # deadline enforcement at the admission boundary: a request
            # whose deadline lapsed while queued retires as timed_out
            # instead of occupying a lane (the router also expires its
            # own queue — this covers push policies that dispatch
            # eagerly, and bare engines; see docs/fault_tolerance.md)
            while self.queue:
                req = self.queue[0]
                if (req.deadline_s is None
                        or time.perf_counter() - req.submitted
                        <= req.deadline_s):
                    break
                self.queue.popleft()
                req.status = "timed_out"
                req.finished = time.perf_counter()
                self.done[req.uid] = req
            if not self.queue:
                break
            plen = len(req.prompt)
            pb = self._bucket_for(plen)
            if plen > pb:
                req.truncated = True
                if not self._warned_truncation:
                    warnings.warn(
                        f"prompt of request {req.uid} ({plen} tokens) "
                        f"exceeds the largest bucket ({pb}); keeping the "
                        f"last {pb} tokens (warned once per engine)")
                    self._warned_truncation = True
            need = min(pb + req.max_new, self.max_seq)
            toks = np.zeros((1, pb), np.int32)
            pr = req.prompt[-pb:]
            toks[0, pb - len(pr):] = pr
            # prefix sharing: the chain keys the BUCKETED row (padding
            # included) — page bytes are a pure function of the padded
            # prefix, so only identical padded prefixes may alias
            chain = (kv_cache.prefix_chain(toks[0], self.page_size)
                     if self.prefix_sharing else None)
            admit_ok = (self.backend.can_admit(need, chain=chain,
                                               prompt_tokens=pb)
                        if chain is not None
                        else self.backend.can_admit(need))
            if not admit_ok:
                break            # retirements will free pages; retry later
            self.queue.popleft()
            self._admit_one(i, req, toks, need, chain)
            admitted += 1
        return admitted

    @runs_on("worker")
    def _admit_one(self, i: int, req: Request, toks: np.ndarray, need: int,
                   chain):
        """Prefill `req` (its bucketed row `toks`) into free lane `i`,
        splice its K/V into the cache and pick its first token."""
        tel = self.telemetry
        pb = toks.shape[1]
        with tel.span("repro.engine.admit", uid=req.uid,
                      bucket=pb) as span:
            tel.record("repro.request.queued", req.submitted, span.t0,
                       uid=req.uid)
            # zero-recompute path: every prompt page resident AND the
            # full-prompt prefill outputs cached -> skip the prefill
            # dispatch and the K/V scatter entirely.  Probe and write
            # run back to back on this worker thread, so a hit cannot
            # go stale in between.
            cached = None
            if chain is not None \
                    and self.backend.shared_hits(chain) == len(chain):
                cached = self._prefill_cache.get(chain[-1])
            sc = lane = first = None
            if cached is not None:
                self._prefill_cache.move_to_end(chain[-1])
                self.prefill_cache_hits += 1
                logits, sc = cached
            elif self._jit_prefill_moe is not None:
                logits, lane, first = self._jit_prefill_moe(
                    self.params, self.dsg, jnp.asarray(toks), self._lane0,
                    np.int32(min(len(req.prompt), pb)))
            elif self.dsg_rt is not None:
                # the prompt's last-token DRS scores seed the lane's CSR
                # pattern: the lane decodes sparsely from step one (a
                # dense warm-in would dilute the modeled FLOP reduction)
                logits, lane, sc = self._jit_prefill_dsg(
                    self.params, self.dsg, jnp.asarray(toks), self._lane0)
            else:
                logits, lane = self._jit_prefill(self.params, self.dsg,
                                                 jnp.asarray(toks),
                                                 self._lane0)
            self.cache = self.backend.write(self.cache, lane, i,
                                            n_tokens=pb, reserve_tokens=need,
                                            chain=chain)
            tel.count("kv.rows_written", pb)
            # _draws advances for every admission so the sampling key
            # schedule doesn't depend on how many greedy requests preceded
            self._draws += 1
            if req.temperature > 0:
                tok = self._jit_first(logits, self._base_key, self._draws,
                                      np.float32(req.temperature),
                                      np.float32(req.top_p))
            elif first is None:
                tok = jnp.argmax(logits)
            else:
                tok = None          # the greedy token leads `first`
            req.started = time.perf_counter()
            # the host's reads of the admission's device values wait for
            # the prefill
            with tel.span("repro.engine.first_token"):
                got = None if first is None else np.asarray(first)
                self._next_tok[i] = int(got[0] if tok is None else tok)
                sc_np = None if sc is None else np.asarray(sc)
            if got is not None:
                span.set(**self._count_moe(got[1:]))
            if self.dsg_rt is not None:
                self.dsg_rt.set_lane_from_scores(i, sc_np[:, 0])
            if chain is not None and cached is None:
                self._remember_prefill(chain[-1], logits, sc_np)
            self.slots[i].req = req
            self.slots[i].pos = pb

    def _kv_blocks(self, plan: StepPlan) -> int:
        """Blocks of pages the paged decode kernel walks in one layer of
        this step, summed over lanes (kernels/paged_attention.py), from
        the lanes' depths and the kernel's own block size; 0 where no
        kernel walks pages.  A fused chunk counts its first micro-step."""
        if self.cache.kind != "paged" or not _use_paged_kernel(
                self.cfg.paged_attn_kernel):
            return 0
        pool = next(iter(kv_cache.pools_of(self.cache.data).values()))
        _, _, ps, *row = pool.shape
        kv, d = row if len(row) == 2 else (1, row[0])   # K/V, or MLA rows
        block = paged_attention.pages_per_block(
            ps, kv, d, pool.dtype, self.cache.data["page_table"].shape[1])
        return paged_attention.walk_blocks(plan.pos, ps, block)

    @staticmethod
    def _kv_rows(plan: StepPlan) -> int:
        """Keys the step's attention reads at the active lanes' true
        depths, summed over lanes: a lane writing at `pos` attends rows
        0..pos.  A fused chunk counts its first micro-step."""
        return int((plan.pos[plan.active] + 1).sum()) if plan.active else 0

    def idle_plan(self) -> StepPlan:
        """A plan with no active lane: every lane mirrors donor 0 at
        position 0, so its decode writes land in the scratch page (paged)
        or in lane bytes the next admission fully overwrites (dense), and
        nothing ever reads them.  warm_decode dispatches it, and the
        sharded executor gives it to a replica with nothing to decode."""
        n = self.n_slots
        return StepPlan(active=[], donor=0, tok=np.zeros(n, np.int32),
                        pos=np.zeros(n, np.int32),
                        free_mask=np.ones(n, np.bool_),
                        temps=np.zeros(n, np.float32),
                        top_ps=np.ones(n, np.float32), sample=False,
                        chunk=self.decode_chunk,
                        eos_ids=np.full(n, -1, np.int32),
                        emit_left=np.ones(n, np.int32))

    def decode_operands(self, plan: StepPlan, sample: bool) -> tuple:
        """The operands of this engine's decode program for `plan`, after
        params and dsg, in make_decode_fns' order: the lanes' tokens,
        the cache, their positions and free mask and the donor lane (a
        fused chunk's tokens, cache, positions, done mask, emit budgets
        and EOS ids), the DSG CSR operand at the plan's width (None
        without DSG), then, when `sample`, the PRNG key, the step and the
        lanes' temperatures and top-ps.  PRNG keys depend only on
        (engine seed, step, lane), so mixing greedy-only and sampling
        steps never shifts the key schedule."""
        if plan.chunk > 1:
            lanes = (jnp.asarray(plan.tok), self.cache, jnp.asarray(plan.pos),
                     jnp.asarray(plan.free_mask),
                     jnp.asarray(plan.emit_left), jnp.asarray(plan.eos_ids))
        else:
            lanes = (plan.tok, self.cache, jnp.asarray(plan.pos),
                     plan.free_mask, plan.donor, self._tok_dev)
        csr = (None if self.dsg_rt is None
               else self.dsg_rt.device_csr(plan.bound))
        # an ahead plan is dispatched while the step before it is
        # uncommitted, so its index is one past the committed count
        keys = ((self._base_key, self.steps + plan.ahead, plan.temps,
                 plan.top_ps) if sample else ())
        return lanes + (csr,) + keys

    @runs_on("worker")
    def warm_decode(self, sample: bool = False):
        """Compile every decode program this engine can dispatch, so no
        compile lands inside a measured decode window: the greedy one
        (and with `sample` the sampled one), and for a DSG engine each
        at every CSR width its runtime can reach, with and without the
        refresh scores.  Dispatches real decode steps on the idle plan
        (`idle_plan`), whose writes nothing reads, after committing the
        step in flight."""
        self._settle()
        rt = self.dsg_rt
        for bound, refresh, samp in itertools.product(
                (0,) if rt is None else rt.warm_bounds(),
                (False,) if rt is None else (False, True),
                (False, True)[:1 + sample]):
            plan = dataclasses.replace(self.idle_plan(), bound=bound)
            *outs, self.cache, _ = self._jit_decode[samp](
                self.params, self.dsg, *self.decode_operands(plan, samp),
                refresh=refresh)
            self._tok_dev = outs[-1]

    @runs_on("worker")
    def begin_step(self) -> Optional[StepPlan]:
        """Host half of a decode step: admit queued prompts, emit each
        active lane's pending token, grow page tables for this step's
        write positions, and build the decode operands.  Returns None
        when no lane is active (and raises if prompts are queued but can
        never be admitted).  Callers must follow a non-None plan with
        the jitted decode dispatch and `commit_step()` — the synchronous
        composition that replica executors batch the middle of; `step()`
        keeps a decode in flight instead.  Commits the step `step()` left
        in flight first."""
        self._settle()
        with self.telemetry.span("repro.engine.begin"):
            self._boundary()
            return self._plan(None)

    @runs_on("worker")
    def _boundary(self) -> None:
        """The step boundary's fault hooks: a pending abort, then the
        chaos harness."""
        if self.abort:
            # cleared here (not left sticky) so a restarted replica does
            # not immediately re-abort; ServingEngine.reset() also clears
            self.abort = False
            raise EngineAborted(
                f"replica {self.replica_index} aborted at step boundary "
                f"(stall-timeout containment)")
        if self.fault_injector is not None:
            # chaos harness hook: kill raises here (before this step's
            # tokens land), delay sleeps inside the step, poison corrupts
            # resident outputs then raises — see runtime/fault_tolerance
            self.fault_injector.on_step(self)

    @runs_on("worker")
    def _finished(self, i: int) -> bool:
        """Whether lane `i`'s stream is complete: its last token is its
        EOS, it holds `max_new` tokens, or its next write position is past
        the cache.  The lane still decodes that last token once, and
        leaves at the commit of that decode."""
        slot = self.slots[i]
        r = slot.req
        return ((r.eos_id is not None and r.output[-1] == r.eos_id)
                or len(r.output) >= r.max_new or slot.pos >= self.max_seq)

    @runs_on("worker")
    def _plan(self, inflight: Optional[StepPlan]) -> Optional[StepPlan]:
        """Build the next decode's plan.  With no decode in flight: admit,
        and every resident lane decodes its host token, emitted now.
        Ahead of the decode `inflight`: no admission; each of its lanes
        whose stream goes on decodes its token from the device, emitted
        when `inflight` is committed, and any other resident lane its
        host token."""
        ahead = inflight is not None
        admits = 0 if ahead else self._admit()
        fed = set(inflight.active) if ahead else set()
        active = [i for i, s in enumerate(self.slots)
                  if not s.free and not (i in fed and self._finished(i))]
        if not active:
            if self.queue and not ahead:
                raise RuntimeError(
                    "engine stalled: queued prompts cannot be admitted — "
                    "the paged cache pool is smaller than a single "
                    "request's page reservation; raise cache_tokens or "
                    "lower max_new/prompt_bucket")
            return None
        # Free/retired lanes mirror the first active lane instead of feeding
        # an arbitrary pad token: with the paper's inter-sample threshold
        # sharing (DRS threshold_mode="shared", taken from batch row 0) an
        # idle lane 0 would otherwise drive every live lane's sparsity mask
        # with junk.  Mirrored lanes emit nothing; their K/V scribbles land
        # in a lane that the next admission fully overwrites (dense) or in
        # the donor's own pages as identical duplicates (paged — see
        # kv_cache.decode_view) and are never observed.  The decode program
        # mirrors their tokens; their positions are mirrored here.
        donor = active[0]
        live = set(active)
        tok = np.array(self._next_tok, np.int32)
        tok[list(fed & live)] = -1
        pos = np.empty(self.n_slots, np.int32)
        free_mask = np.zeros(self.n_slots, np.bool_)
        temps = np.zeros(self.n_slots, np.float32)
        top_ps = np.ones(self.n_slots, np.float32)
        C = self.decode_chunk
        eos_ids = np.full(self.n_slots, -1, np.int32)
        emit_left = np.ones(self.n_slots, np.int32)
        pushes = 0                  # page-table pushes to the device
        with self.telemetry.span("repro.kv.grow") as grow:
            for i, s in enumerate(self.slots):
                if i not in live:
                    free_mask[i] = True
                    pos[i] = self.slots[donor].pos
                elif C == 1:
                    pos[i] = s.pos
                    temps[i] = s.req.temperature
                    top_ps[i] = s.req.top_p
                    # page-table growth for this step's write position (no-op
                    # for the dense backend or when the page is already mapped)
                    cache = self.backend.ensure(self.cache, i, s.pos)
                    pushes += cache is not self.cache
                    self.cache = cache
                else:
                    pos[i] = s.pos
                    temps[i] = s.req.temperature
                    top_ps[i] = s.req.top_p
                    r = s.req
                    eos_ids[i] = -1 if r.eos_id is None else r.eos_id
                    emit_left[i] = r.max_new - len(r.output)
                    # the fused chunk cannot grow the page table mid-scan, so
                    # `ensure` moves ahead of the loop: pre-map every page the
                    # lane can write this chunk.  Clamping to the lane's own
                    # emit budget / max_seq headroom keeps the mapping inside
                    # its admission-time reservation (ensure stays infallible)
                    w = min(C, int(emit_left[i]), self.max_seq - s.pos)
                    cache = self.backend.ensure_range(self.cache, i,
                                                      s.pos, s.pos + w)
                    pushes += cache is not self.cache
                    self.cache = cache
            grow.set(pushes=pushes)
        if C == 1:
            for i in active:
                s = self.slots[i]
                s.pos += 1                 # the write is dispatched now
                if tok[i] >= 0:
                    if not s.req.output:
                        s.req.first_token = time.perf_counter()   # TTFT
                    s.req.output.append(int(tok[i]))
        # else emission happens on device and commit_chunk appends
        rt = self.dsg_rt
        refresh, bound = False, 0
        if rt is not None:
            # a lane is due for a DSG refresh when its emitted-token count
            # crosses refresh_interval — its own history only, so streams
            # stay invariant to co-scheduling and replica count.  In a
            # fused chunk the due point can only land on the last
            # micro-step (refresh_interval % chunk == 0 and lanes admit
            # at chunk boundaries): predict it here.  Lanes that would
            # freeze on budget/max_seq before the last micro-step never
            # reach their due token; an unpredicted EOS freeze just
            # wastes one score read.
            R = rt.cfg.refresh_interval
            bound = rt.bound()
            if C == 1:
                refresh = any(len(self.slots[i].req.output) % R == 0
                              for i in active)
            else:
                refresh = any(
                    (len(self.slots[i].req.output) + C) % R == 0
                    and int(emit_left[i]) >= C
                    and self.max_seq - self.slots[i].pos >= C
                    for i in active)
        return StepPlan(active=active, donor=donor, tok=tok, pos=pos,
                        free_mask=free_mask, temps=temps, top_ps=top_ps,
                        sample=bool((temps > 0).any()), chunk=C,
                        eos_ids=eos_ids, emit_left=emit_left,
                        refresh=refresh, bound=bound, admits=admits,
                        ahead=ahead)

    @runs_on("worker")
    def commit_step(self, plan: StepPlan, next_tok: np.ndarray,
                    seconds: float) -> dict:
        """Record a decode result: latch each lane's next input token,
        account the device time/tokens, and retire the lanes whose last
        token this step decoded.  When a later plan already decodes these
        tokens from the device (`plan.then`), they are emitted here, and a
        stream they complete finishes now; its lane leaves at the commit
        of that later decode, so no page is freed under a step that
        writes it.  `next_tok` must already be host-side (the caller
        syncs — that is where the device wait belongs in the timing).  A
        MoE step's counts follow the tokens (make_decode_fns); they are
        counted and returned as span attrs (empty for a dense model)."""
        with self.telemetry.span("repro.engine.commit") as span:
            self._next_tok = np.array(next_tok[:self.n_slots], np.int32)
            self.decode_seconds += seconds
            self.decode_tokens += len(plan.active)
            self.steps += 1
            then = plan.then
            fed = [] if then is None else [i for i in then.active
                                           if then.tok[i] < 0]
            span.set(retired=len(self._retire(
                [i for i in plan.active if i not in fed])))
            for i in fed:
                r = self.slots[i].req
                r.output.append(int(next_tok[i]))
                if r.eos_id is not None and r.output[-1] == r.eos_id:
                    # the decode in flight was fed the stop token unseen;
                    # its output is discarded at its commit
                    self.telemetry.count("engine.lanes_past_eos")
                if self._finished(i):
                    self._finish_request(r)
            return self._count_moe(next_tok[self.n_slots:])

    @runs_on("worker")
    def _count_moe(self, stats: np.ndarray) -> dict:
        """A MoE forward's counts (rows routed to held experts, held
        experts hit, largest group; sums over the MoE layers) into the
        running counters `moe.rows` and `moe.experts_hit`; returns them
        as span attrs.  Empty for a dense model's empty `stats`."""
        if not len(stats):
            return {}
        rows, hit, most = (int(v) for v in stats)
        self.telemetry.count("moe.rows", rows)
        self.telemetry.count("moe.experts_hit", hit)
        return {"moe_rows": rows, "moe_experts_hit": hit,
                "moe_rows_max": most}

    @runs_on("worker")
    def _retire(self, lanes) -> list:
        """Retire each of `lanes` whose request is finished — after its
        EOS token has been emitted, so a stop token always appears in the
        output it terminates — and return their pages; returns the lanes
        retired."""
        retired = []
        for i in lanes:
            slot = self.slots[i]
            if self._finished(i):
                self._finish_request(slot.req)
                slot.req = None
                slot.pos = 0
                self.cache = self.backend.free(self.cache, i)
                retired.append(i)
        return retired

    @runs_on("worker")
    def _finish_request(self, r: Request) -> None:
        """Stamp a complete stream `ok` and file it in `done` (once: a
        stream completed ahead of its last decode keeps its stamp)."""
        if r.status == "pending":
            r.status = "ok"
            r.finished = time.perf_counter()
            self.done[r.uid] = r

    @runs_on("worker")
    def commit_chunk(self, plan: StepPlan, blk: np.ndarray,
                     flags: np.ndarray, next_tok: np.ndarray,
                     seconds: float) -> dict:
        """Record a fused decode chunk: append each lane's emitted tokens
        (a lane's flag column is a monotone prefix — once frozen it emits
        nothing more), latch pending next-step tokens, advance `steps` by
        the micro-steps that had a live lane, and retire finished lanes.
        Host bookkeeping lags a full chunk behind the device; retirement
        re-derives the freeze conditions from the appended output, which
        mirrors the device's done logic exactly (EOS == output[-1],
        len(output) >= max_new, pos >= max_seq).  A MoE chunk's counts
        follow the tokens, as in commit_step."""
        with self.telemetry.span("repro.engine.commit") as span:
            emitted = 0
            for i in plan.active:
                slot = self.slots[i]
                n = int(flags[:, i].sum())
                if n and not slot.req.output:
                    # TTFT stamp at host observation time: the token left
                    # the device mid-chunk, but commit is when a caller
                    # could first stream it — the honest latency for a
                    # fused loop
                    slot.req.first_token = time.perf_counter()
                slot.req.output.extend(int(t) for t in blk[:n, i])
                slot.pos += n
                emitted += n
            self._next_tok = np.array(next_tok[:self.n_slots], np.int32)
            self.decode_seconds += seconds
            self.decode_tokens += emitted
            self.steps += int(flags.any(axis=1).sum())
            span.set(retired=len(self._retire(plan.active)))
            return self._count_moe(next_tok[self.n_slots:])

    @runs_on("worker")
    def commit(self, plan: StepPlan, outs, seconds: float) -> dict:
        """Record the host copies `outs` of a decode program's outputs
        (all but the cache and the scores): `(next_tok,)` for a single
        step (commit_step), `(blk, flags, next_tok)` for a fused chunk
        (commit_chunk).  Returns the step span's MoE attrs."""
        if plan.chunk > 1:
            return self.commit_chunk(plan, *outs, seconds)
        return self.commit_step(plan, outs[0], seconds)

    @runs_on("worker")
    def _settle_dsg(self, plan: StepPlan, outs, scores) -> None:
        """DSG host bookkeeping after a commit: the FLOP-model entry of
        each micro-step over the lanes live in it (a fused chunk's
        counters match a chunk=1 run of the same traffic), the retired
        lanes' patterns reset, and the due lanes' patterns rewritten from
        the refresh scores.  The host's pattern state lags the device
        step, as the page table does."""
        rt = self.dsg_rt
        live = ([plan.active] if plan.chunk == 1
                else [[i for i in plan.active if f[i]] for f in outs[1]])
        for lanes in live:
            if lanes:
                rt.record_step(lanes, plan.bound)
        for i in plan.active:
            if self.slots[i].req is None:          # retired in commit
                rt.reset_lane(i)
        if scores is not None:
            R = rt.cfg.refresh_interval
            rt.update_from_scores(scores, [
                i for i in plan.active if self.slots[i].req is not None
                and len(self.slots[i].req.output) % R == 0])

    @runs_on("worker")
    def step(self):
        """One engine step.  A pipelined engine keeps one decode in
        flight: it builds and dispatches decode n+1 on decode n's tokens,
        still on the device, and only then reads and commits decode n, so
        its host work runs under the device's.  It reads decode n first
        (a drain) where the synchronous order would admit a prompt, and
        commits it without a successor once no lane goes on.  A DSG or
        fused-chunk engine dispatches and commits each step in turn
        (`_pipelined`)."""
        tel = self.telemetry
        with tel.span("repro.engine.step", step=self.steps) as span:
            self._boundary()
            attrs = dict(self._moe_none)
            prev = self._inflight
            if prev is not None and self._admits_next(prev.plan):
                self._inflight = None
                attrs.update(self._finish(prev))
                prev = None
            with tel.span("repro.engine.begin"):
                plan = self._plan(None if prev is None else prev.plan)
            span.set(lanes=len(plan.active) if plan else 0,
                     admits=plan.admits if plan else 0,
                     ahead=int(plan is not None and plan.ahead))
            launch = None
            if plan is not None:
                if plan.ahead:
                    prev.plan.then = plan
                    tel.count("engine.ahead_steps")
                launch = self._launch(plan)
            self._inflight = launch if self._pipelined else None
            if prev is not None:
                attrs.update(self._finish(prev))
            if launch is not None and not self._pipelined:
                attrs.update(self._finish(launch))
            span.set(**attrs)

    @runs_on("worker")
    def _admits_next(self, inflight: StepPlan) -> bool:
        """Whether the synchronous order would admit a prompt before the
        next decode, judged before the decode in flight is read: a prompt
        is queued and a lane leaves at that decode's commit, or a lane is
        free and the queue's head fits (or has expired, which `_admit`
        settles)."""
        if not self.queue:
            return False
        if any(self._finished(i) for i in inflight.active):
            return True
        head = self.queue[0]
        expired = (head.deadline_s is not None and
                   time.perf_counter() - head.submitted > head.deadline_s)
        return self.free_slots() > 0 and (
            expired or self.can_admit_request(head))

    @runs_on("worker")
    def _launch(self, plan: StepPlan) -> _InFlight:
        """Dispatch `plan`'s decode (enqueue only)."""
        self.telemetry.count("kv.rows_written",
                             len(plan.active) * plan.chunk)
        with self.telemetry.span("repro.engine.dispatch",
                                 kv_blocks=self._kv_blocks(plan),
                                 kv_rows=self._kv_rows(plan)) as disp:
            *outs, self.cache, scores = self._jit_decode[plan.sample](
                self.params, self.dsg,
                *self.decode_operands(plan, plan.sample),
                refresh=plan.refresh)
        self._tok_dev = outs[-1]
        return _InFlight(plan, outs, scores, disp.seconds)

    @runs_on("worker")
    def _finish(self, launch: _InFlight) -> dict:
        """Read a dispatched decode's outputs and commit it.
        `decode_seconds` gains its dispatch and the wait for its outputs,
        the spans `repro.engine.dispatch` and `repro.engine.sync`.  A read
        that no later dispatch is queued behind counts as a drain.
        Returns the step span's MoE attrs (commit_step)."""
        plan, outs, scores, dispatch_s = launch
        tel = self.telemetry
        with tel.span("repro.engine.sync") as sync:
            outs = [np.asarray(o) for o in outs]
        if plan.then is None:
            tel.count("engine.drains")
        attrs = self.commit(plan, outs, dispatch_s + sync.seconds)
        if self.dsg_rt is not None:
            self._settle_dsg(plan, outs, scores)
        return attrs

    @runs_on("worker")
    def _settle(self) -> dict:
        """Read and commit the decode in flight, if any."""
        launch, self._inflight = self._inflight, None
        return {} if launch is None else self._finish(launch)

    # -- fault containment (called by serving/router.py failover) ------------
    #
    # These run under the "worker" role like every other engine mutation.
    # During failover the replica's own worker is gone (it raised and
    # exited, or never existed under the lockstep executors), so the
    # router thread is momentarily the engine's driver — the threaded
    # executor serializes that handoff under its condition lock and, with
    # REPRO_TSAN=1, re-resolves the role to quiescent before the router
    # touches the engine.

    @runs_on("worker")
    def evict_slot(self, i: int) -> Optional[Request]:
        """Release lane `i` mid-flight and return its request (None when
        the lane is free): the lane's pages return to the backend pool
        and its DSG pattern resets, exactly as retirement would, but the
        request does NOT land in `done`.  The partial output is kept —
        the caller decides between replay (the router's failover clears
        it so re-decode from the prompt is bit-identical at temperature
        0) and surfacing the partial stream.  The decode in flight is
        committed first; a lane whose stream completed ahead of its last
        decode is released and returns None, its request being done."""
        self._settle()
        slot = self.slots[i]
        req = slot.req
        if req is None:
            return None
        slot.req = None
        slot.pos = 0
        self.cache = self.backend.free(self.cache, i)
        if self.dsg_rt is not None:
            self.dsg_rt.reset_lane(i)
        return req if req.status == "pending" else None

    @runs_on("worker")
    def evict_request(self, uid: int) -> Optional[Request]:
        """Evict request `uid` wherever it sits — a resident lane (freed
        via evict_slot) or the admission queue.  Returns the request, or
        None when it is not on this engine (already retired or never
        dispatched here)."""
        for i, slot in enumerate(self.slots):
            if slot.req is not None and slot.req.uid == uid:
                return self.evict_slot(i)
        for req in self.queue:
            if req.uid == uid:
                self.queue.remove(req)
                return req
        return None

    @runs_on("worker")
    def reset(self) -> List[Request]:
        """Reclaim every queued + resident request and return them in
        admission order (resident lanes by slot index — they were
        admitted first — then the queue FIFO).  `done` is preserved:
        requests that retired before the failure completed correctly.
        The engine itself stays warm (compiled callables, cache pool,
        PRNG base key) — this IS the replica restart path; a restarted
        replica serves its next request with no recompilation.  The
        decode in flight is discarded unread: the failure path must not
        wait on a device that may be at fault, and every lane it wrote is
        reclaimed here."""
        self._inflight = None
        reclaimed = []
        for i in range(self.n_slots):
            req = self.evict_slot(i)
            if req is not None:
                reclaimed.append(req)
        reclaimed.extend(self.queue)
        self.queue.clear()
        self._next_tok[:] = 0
        self.abort = False
        return reclaimed

    # -- stats ---------------------------------------------------------------

    def throughput(self) -> float:
        """End-to-end tok/s over the span from first ADMISSION to last
        finish.  (An earlier version divided by the submit->finish span,
        which charges the engine for queue wait accrued before it ever
        ran — e.g. requests submitted long before run().)

        Raises ValueError before any request has finished: there is no
        admission->finish window yet, and the old 0.0 return read as "the
        engine is infinitely slow" in benchmark ratios."""
        if not self.done:
            raise ValueError(
                "throughput() needs at least one finished request; "
                "run the engine (or drain()) before reading stats")
        toks = sum(len(r.output) for r in self.done.values())
        t0 = min(r.started or r.submitted for r in self.done.values())
        t1 = max(r.finished for r in self.done.values())
        return toks / max(t1 - t0, 1e-9)

    def decode_tok_per_s(self) -> float:
        """Decode-only rate: emitted tokens over time spent inside the
        jitted decode step (excludes admission/prefill and host
        scheduling), the number to watch for cache-backend regressions.

        Raises ValueError before any decode step has emitted a token
        (same contract as throughput())."""
        if not self.decode_tokens:
            raise ValueError(
                "decode_tok_per_s() needs at least one decoded token; "
                "run the engine before reading stats")
        return self.decode_tokens / max(self.decode_seconds, 1e-9)

    def latencies(self) -> np.ndarray:
        """Per-request completion latency (submit -> finish) in seconds."""
        return np.array(sorted(r.finished - r.submitted
                               for r in self.done.values()))
