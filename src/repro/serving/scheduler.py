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

`admission="wave"` preserves the old drain-then-refill policy (admit only
when every lane is free) as a benchmark baseline — bench_serving.py
measures the overlap speedup against it on mixed-length traffic.

This is the single-host engine; at pod scale the same slot logic runs
per data-parallel replica group with the model sharded over 'model'
(the decode step is already the dry-run-verified sharded function).
"""
from __future__ import annotations

import collections
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

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

def live_page_bound(max_pos: int, page_size: int, max_pages: int) -> int:
    """Static paged-decode walk bound covering a batch whose deepest lane
    writes at max_pos: pages needed, rounded up to a power of two so the
    decode step compiles at most log2(max_pages) variants instead of one
    per depth, capped at the page-table width."""
    need = max_pos // page_size + 1
    return min(1 << (need - 1).bit_length(), max_pages)


def live_page_buckets(max_pages: int) -> tuple:
    """Every bound live_page_bound can return for a given table width —
    the set warm_decode pre-compiles and traffic models enumerate."""
    return tuple(sorted({min(1 << i, max_pages)
                         for i in range(max_pages.bit_length() + 1)}))


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
    one vmapped decode — while `ServingEngine.step()` stays the
    single-engine begin -> dispatch -> commit composition.
    """
    active: List[int]                 # slot indices decoding this step
    donor: int                        # active lane free lanes mirror
    tok: np.ndarray                   # (n_slots,) int32 decode inputs
    pos: np.ndarray                   # (n_slots,) int32 write positions
    free_mask: np.ndarray             # (n_slots,) bool
    temps: np.ndarray                 # (n_slots,) float32
    top_ps: np.ndarray                # (n_slots,) float32
    live_pages: int                   # static paged walk bound (0 = dense)
    sample: bool                      # any lane with temperature > 0
    # fused-chunk dispatch (decode_chunk > 1): `chunk` micro-steps run in
    # one device dispatch, with per-lane EOS / emit-budget freezing on
    # device, so begin_step emits nothing and commit_chunk lags a full
    # chunk behind.  eos_ids uses -1 for "no stop token".
    chunk: int = 1
    eos_ids: Optional[np.ndarray] = None   # (n_slots,) int32
    emit_left: Optional[np.ndarray] = None  # (n_slots,) int32 budget
    refresh: bool = False             # DSG: collect scores at last micro-step
    admits: int = 0                   # prompts admitted by this begin_step


def _restore_table(data, c):
    # the host mirror is the source of truth for the page table;
    # the lane-mirrored view must not escape the step
    if c.kind != "paged":
        return data
    return {**data, "page_table": c.data["page_table"]}


def make_decode_fns(cfg):
    """Build the (greedy, sample) decode-step callables the engine jits.

    Module-level (rather than closures in `ServingEngine.__init__`) so
    the sharded replica executor can vmap THE SAME step bodies over a
    leading replica axis — one definition, two compilation strategies,
    no drift between the per-engine and batched paths.

    A MoE model's step returns the next tokens (B,) followed by its three
    expert-layer counts over the active lanes (models/moe.py,
    `moe_ffn_dropless`), (B + 3,) int32, so that they reach the host in
    the tokens' transfer; `commit_step` splits them.
    """
    def _step(p, d, tok, c, pos, free_mask, donor, live_pages):
        view = kv_cache.decode_view(c, free_mask, donor)
        if not cfg.is_moe:
            logits, data = api.decode_step(p, d, cfg, tok, view, pos,
                                           live_pages=live_pages)
            return logits, data, None
        return api.decode_step(p, d, cfg, tok, view, pos,
                               live_pages=live_pages,
                               moe_count=~free_mask[:, None])

    def _out(nxt, stats, data, c):
        if stats is not None:
            nxt = jnp.concatenate([nxt, stats])
        return nxt, CacheHandle(_restore_table(data, c), c.kind,
                                c.page_size)

    def _decode_greedy(p, d, tok, c, pos, free_mask, donor, live_pages):
        logits, data, stats = _step(p, d, tok, c, pos, free_mask, donor,
                                    live_pages)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return _out(nxt, stats, data, c)

    def _decode_sample(p, d, tok, c, pos, free_mask, donor, live_pages,
                       key, step, temps, top_ps):
        logits, data, stats = _step(p, d, tok, c, pos, free_mask, donor,
                                    live_pages)
        keys = jax.random.split(jax.random.fold_in(key, step),
                                tok.shape[0])
        nxt = sample_tokens(logits, keys, temps, top_ps)
        return _out(nxt, stats, data, c)

    return _decode_greedy, _decode_sample


def make_dsg_decode_fns(cfg):
    """DSG-serving decode-step variants (engines with a DSGRuntime):
    the make_decode_fns bodies plus (a) the group-CSR selection operand
    `csr` = {'idx': (L, B, K), 'counts': (L, B)} — free lanes mirror the
    donor's rows in-jit (dsg_runtime.mirror_csr) so paged duplicate K/V
    writes stay bit-identical — and (b) a python-static `refresh` flag
    that additionally returns each layer's DRS group scores of this
    step's FFN inputs (None otherwise); the runtime rewrites due lanes'
    patterns from them AFTER the step, off the measured decode window.
    K is static (pow2 active-group bound), so the decode compiles
    (bounds x refresh) variants, all pre-compiled by warm_decode."""
    from repro.serving.dsg_runtime import mirror_csr

    def _dsg_greedy(p, d, tok, c, pos, free_mask, donor, live_pages, csr,
                    refresh):
        view = kv_cache.decode_view(c, free_mask, donor)
        csr_m = mirror_csr(csr, free_mask, donor)
        out = api.decode_step(p, d, cfg, tok, view, pos,
                              live_pages=live_pages, ffn_csr=csr_m,
                              collect_drs_scores=refresh)
        if refresh:
            logits, data, scores = out
        else:
            (logits, data), scores = out, None
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, CacheHandle(_restore_table(data, c), c.kind,
                                 c.page_size), scores)

    def _dsg_sample(p, d, tok, c, pos, free_mask, donor, live_pages, csr,
                    key, step, temps, top_ps, refresh):
        view = kv_cache.decode_view(c, free_mask, donor)
        csr_m = mirror_csr(csr, free_mask, donor)
        out = api.decode_step(p, d, cfg, tok, view, pos,
                              live_pages=live_pages, ffn_csr=csr_m,
                              collect_drs_scores=refresh)
        if refresh:
            logits, data, scores = out
        else:
            (logits, data), scores = out, None
        keys = jax.random.split(jax.random.fold_in(key, step),
                                tok.shape[0])
        nxt = sample_tokens(logits, keys, temps, top_ps)
        return (nxt, CacheHandle(_restore_table(data, c), c.kind,
                                 c.page_size), scores)

    return _dsg_greedy, _dsg_sample


def make_chunked_decode_fns(cfg, chunk: int, max_seq: int):
    """Build the (greedy, sample) FUSED decode-chunk callables: `chunk`
    decode steps scanned inside one jitted dispatch, so the per-token
    host sync (the dispatch-bound wall BENCH_paged_decode.json measures)
    is paid once per chunk instead of once per token.

    The scan carry keeps (tok, pos, done, emit_left, cache) on device.
    Per micro-step, lanes whose done bit is set (initially the free
    lanes; later any lane that hit EOS / its max_new budget / max_seq)
    mirror the first live lane exactly like the chunk=1 donor path —
    `jnp.argmin(done)` re-picks the donor every micro-step because the
    chunk=1 donor (first active lane) can itself finish mid-chunk.  A
    frozen lane's writes are donor duplicates (paged) or overwritten at
    readmission (dense), identical to the chunk=1 free-lane contract.

    Outputs: `blk` (chunk, n_slots) int32 — the token each lane emitted
    at each micro-step (its decode INPUT, matching begin_step's
    emit-before-decode order at chunk=1) — and `flags` (chunk, n_slots)
    bool marking which entries are real.  A lane's flag column is a
    monotone prefix: done never unsets, so the host takes `blk[:n, i]`.
    The final carry's tok is the lane's pending next-step token.

    The sample variant folds the key schedule as (seed, step0 + k,
    lane) — bitwise the per-step schedule, so a sampled lane's stream
    is invariant to the chunk size AS LONG AS its admission step and
    `_draws` count match (chunked scheduling admits at chunk boundaries,
    which shifts admission timing under load; temperature-0 streams are
    unconditionally chunk-invariant).
    """
    def _make(sample):
        def fn(p, d, tok, c, pos, done, emit_left, eos_ids, live_pages,
               *extra):
            if sample:
                key, step0, temps, top_ps = extra

            def body(carry, k):
                tok, pos, done, left, c = carry
                donor = jnp.argmin(done)      # first live lane (False < True)
                tok_in = jnp.where(done, tok[donor], tok)
                pos_in = jnp.where(done, pos[donor], pos)
                view = kv_cache.decode_view(c, done, donor)
                logits, data = api.decode_step(p, d, cfg, tok_in[:, None],
                                               view, pos_in,
                                               live_pages=live_pages)
                if sample:
                    keys = jax.random.split(jax.random.fold_in(key, k),
                                            tok.shape[0])
                    nxt = sample_tokens(logits, keys, temps, top_ps)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                live = ~done
                fin = live & (((eos_ids >= 0) & (tok_in == eos_ids))
                              | (left <= 1) | (pos_in + 1 >= max_seq))
                c = CacheHandle(_restore_table(data, c), c.kind,
                                c.page_size)
                carry = (jnp.where(live, nxt, tok),
                         jnp.where(live, pos_in + 1, pos),
                         done | fin,
                         jnp.where(live, left - 1, left), c)
                return carry, (tok, live)

            xs = (step0 + jnp.arange(chunk)) if sample else None
            carry0 = (tok, pos, done, emit_left, c)
            (tok_f, _, _, _, c_f), (blk, flags) = jax.lax.scan(
                body, carry0, xs, length=chunk)
            return blk, flags, tok_f, c_f
        return fn

    return _make(False), _make(True)


def make_chunked_dsg_decode_fns(cfg, chunk: int, max_seq: int):
    """DSG variants of make_chunked_decode_fns: the CSR pattern operand
    is CONSTANT across the chunk (the engine enforces refresh_interval %
    chunk == 0, and lanes admit at chunk boundaries, so a refresh-due
    point can only land on the LAST micro-step — the same token index at
    which the chunk=1 cadence fires).  The last micro-step runs outside
    the scan with the python-static `refresh` flag so it can return that
    step's DRS group scores for the host-side pattern rewrite."""
    from repro.serving.dsg_runtime import mirror_csr

    def _make(sample):
        def fn(p, d, tok, c, pos, done, emit_left, eos_ids, live_pages,
               csr, *extra):
            if sample:
                key, step0, temps, top_ps, refresh = extra
            else:
                (refresh,) = extra

            def micro(carry, k, collect):
                tok, pos, done, left, c = carry
                donor = jnp.argmin(done)
                tok_in = jnp.where(done, tok[donor], tok)
                pos_in = jnp.where(done, pos[donor], pos)
                view = kv_cache.decode_view(c, done, donor)
                csr_m = mirror_csr(csr, done, donor)
                out = api.decode_step(p, d, cfg, tok_in[:, None], view,
                                      pos_in, live_pages=live_pages,
                                      ffn_csr=csr_m,
                                      collect_drs_scores=collect)
                if collect:
                    logits, data, scores = out
                else:
                    (logits, data), scores = out, None
                if sample:
                    keys = jax.random.split(jax.random.fold_in(key, k),
                                            tok.shape[0])
                    nxt = sample_tokens(logits, keys, temps, top_ps)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                live = ~done
                fin = live & (((eos_ids >= 0) & (tok_in == eos_ids))
                              | (left <= 1) | (pos_in + 1 >= max_seq))
                c = CacheHandle(_restore_table(data, c), c.kind,
                                c.page_size)
                carry = (jnp.where(live, nxt, tok),
                         jnp.where(live, pos_in + 1, pos),
                         done | fin,
                         jnp.where(live, left - 1, left), c)
                return carry, (tok, live), scores

            def body(carry, k):
                carry, ys, _ = micro(carry, k, False)
                return carry, ys

            xs = (step0 + jnp.arange(chunk - 1)) if sample else None
            carry = (tok, pos, done, emit_left, c)
            carry, (blk, flags) = jax.lax.scan(body, carry, xs,
                                               length=chunk - 1)
            k_last = (step0 + chunk - 1) if sample else 0
            carry, (tok_l, live_l), scores = micro(carry, k_last, refresh)
            blk = jnp.concatenate([blk, tok_l[None]], axis=0)
            flags = jnp.concatenate([flags, live_l[None]], axis=0)
            tok_f, _, _, _, c_f = carry
            return blk, flags, tok_f, c_f, scores
        return fn

    return _make(False), _make(True)


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
          "_warned_truncation", "_prefill_cache", "prefill_cache_hits")
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
                 admission: str = "overlap",
                 cache_backend: Union[str, object] = "dense",
                 page_size: int = 16, cache_tokens: Optional[int] = None,
                 seed: int = 0, dsg_serving=None, decode_chunk: int = 1,
                 prefix_sharing: bool = False):
        if admission not in ("overlap", "wave"):
            raise ValueError(f"unknown admission policy {admission!r}")
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
        self.admission = admission
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

        # sampling is fused into the jitted decode step (one device
        # dispatch per step; the tiny-model regime is dispatch-bound, see
        # bench_serving.py) — with a separate greedy-only variant so the
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

        # the engine cache handle is donated: the caller always rebinds
        # self.cache to the result, and donation lets XLA update one
        # lane / one token column in place instead of copying the whole
        # cache every call.  live_pages is static: the paged decode jit
        # compiles one variant per live-page bucket (see _live_pages).
        _decode_greedy, _decode_sample = make_decode_fns(cfg)
        self._jit_prefill = jax.jit(_prefill)
        self._jit_prefill_moe = jax.jit(_prefill_moe) if cfg.is_moe else None
        self._jit_first = jax.jit(_first_tok)
        self._jit_decode_greedy = jax.jit(_decode_greedy,
                                          donate_argnums=(3,),
                                          static_argnums=(7,))
        self._jit_decode_sample = jax.jit(_decode_sample,
                                          donate_argnums=(3,),
                                          static_argnums=(7,))
        # fused decode chunk (ROADMAP: device-resident decode loop) —
        # decode_chunk micro-steps scanned per dispatch; only built when
        # chunking is on, and then the chunk=1 decode jits above are
        # never dispatched (warm_decode warms whichever set is live)
        if decode_chunk > 1:
            _cg, _cs = make_chunked_decode_fns(cfg, decode_chunk, max_seq)
            self._jit_chunk_greedy = jax.jit(_cg, donate_argnums=(3,),
                                             static_argnums=(8,))
            self._jit_chunk_sample = jax.jit(_cs, donate_argnums=(3,),
                                             static_argnums=(8,))

        # DSG serving runtime (serving/dsg_runtime.py): per-lane group-CSR
        # patterns feed a sparse FFN decode; refresh scores ride back out
        # of the refresh-variant decode step
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

            _dsg_greedy, _dsg_sample = make_dsg_decode_fns(cfg)
            self._jit_prefill_dsg = jax.jit(_prefill_dsg)
            self._jit_decode_greedy_dsg = jax.jit(_dsg_greedy,
                                                  donate_argnums=(3,),
                                                  static_argnums=(7, 9))
            self._jit_decode_sample_dsg = jax.jit(_dsg_sample,
                                                  donate_argnums=(3,),
                                                  static_argnums=(7, 13))
            if decode_chunk > 1:
                _dcg, _dcs = make_chunked_dsg_decode_fns(
                    cfg, decode_chunk, max_seq)
                self._jit_chunk_greedy_dsg = jax.jit(
                    _dcg, donate_argnums=(3,), static_argnums=(8, 10))
                self._jit_chunk_sample_dsg = jax.jit(
                    _dcs, donate_argnums=(3,), static_argnums=(8, 14))

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
        while (self.queue or any(not s.free for s in self.slots)) \
                and self.steps < max_steps:
            self.step()
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

        Overlap policy: every free lane refills immediately (subject to
        the paged backend having pages for the request's reservation).
        Wave policy: admission waits until ALL lanes have drained (the old
        baseline)."""
        if self.admission == "wave" and any(not s.free for s in self.slots):
            return 0
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

    def _live_pages(self, pos: np.ndarray, span: int = 1) -> int:
        """Static page-walk bound for this step's paged decode
        (live_page_bound over the DEEPEST lane; free lanes mirror an
        active donor, so the active max covers them).  The attention
        executor reads only these pages — the whole point of the paged
        layout (ROADMAP: read only live pages).  `span` widens the bound
        to cover a fused chunk's deepest write (pos + span - 1); reads
        past a lane's depth are masked, so a wider bound only changes
        which pow2 compile variant runs, never the gathered values."""
        if self.cache.kind != "paged":
            return 0
        deepest = min(int(pos.max()) + span - 1, self.max_seq - 1)
        return live_page_bound(deepest, self.cache.page_size,
                               self.max_seq // self.cache.page_size)

    def _kv_blocks(self, plan: StepPlan) -> int:
        """Blocks of pages the paged decode kernel walks in one layer of
        this step, summed over lanes (kernels/paged_attention.py), from
        the lanes' depths and the kernel's own block size; 0 where no
        kernel walks pages.  A fused chunk counts its first micro-step."""
        if self.cache.kind != "paged" or not _use_paged_kernel(
                self.cfg.paged_attn_kernel):
            return 0
        pool = self.cache.data["pages_k"]
        _, _, ps, kv, d = pool.shape
        block = paged_attention.pages_per_block(
            ps, kv, d, pool.dtype, self.cache.data["page_table"].shape[1])
        return paged_attention.walk_blocks(plan.pos, ps, block)

    @runs_on("worker")
    def warm_decode(self, sample: bool = False):
        """Pre-compile the jitted decode step for every static live-page
        bucket this engine can reach (_live_pages yields the pow2 series
        up to max_pages; dense engines have a single variant), so no
        compile lands inside a measured decode window.  Dispatches real
        decode steps against the idle cache: every lane mirrors donor 0
        and scatters into the scratch page (paged) or into lane bytes the
        next admission fully overwrites (dense) — no later gather
        observes the writes."""
        if self.cache.kind == "paged":
            buckets = live_page_buckets(self.max_seq // self.cache.page_size)
        else:
            buckets = [0]
        tok = jnp.zeros((self.n_slots, 1), jnp.int32)
        pos = jnp.zeros(self.n_slots, jnp.int32)
        free_mask = np.ones(self.n_slots, np.bool_)
        temps = np.full(self.n_slots, 0.5, np.float32)
        top_ps = np.ones(self.n_slots, np.float32)
        if self.decode_chunk > 1:
            # a chunked engine only ever dispatches the fused variants —
            # warm those instead.  All-done lanes mirror lane 0 exactly
            # like the chunk=1 warm (writes land in the scratch page /
            # overwritten lane bytes), and emit nothing.
            tok1 = jnp.zeros(self.n_slots, jnp.int32)
            done = jnp.ones(self.n_slots, bool)
            left = jnp.ones(self.n_slots, jnp.int32)
            eos = jnp.full(self.n_slots, -1, jnp.int32)
            for live in buckets:
                if self.dsg_rt is not None:
                    for bnd in self.dsg_rt.warm_bounds():
                        csr = self.dsg_rt.device_csr(bnd)
                        for refresh in (False, True):
                            _, _, _, self.cache, _ = \
                                self._jit_chunk_greedy_dsg(
                                    self.params, self.dsg, tok1,
                                    self.cache, pos, done, left, eos,
                                    live, csr, refresh)
                            if sample:
                                _, _, _, self.cache, _ = \
                                    self._jit_chunk_sample_dsg(
                                        self.params, self.dsg, tok1,
                                        self.cache, pos, done, left, eos,
                                        live, csr, self._base_key, 0,
                                        temps, top_ps, refresh)
                    continue
                _, _, _, self.cache = self._jit_chunk_greedy(
                    self.params, self.dsg, tok1, self.cache, pos, done,
                    left, eos, live)
                if sample:
                    _, _, _, self.cache = self._jit_chunk_sample(
                        self.params, self.dsg, tok1, self.cache, pos,
                        done, left, eos, live, self._base_key, 0, temps,
                        top_ps)
            return
        for live in buckets:
            if self.dsg_rt is not None:
                # (bound x refresh) variants of the DSG decode step; the
                # plain decode fns are never dispatched by a DSG engine
                for bnd in self.dsg_rt.warm_bounds():
                    csr = self.dsg_rt.device_csr(bnd)
                    for refresh in (False, True):
                        _, self.cache, _ = self._jit_decode_greedy_dsg(
                            self.params, self.dsg, tok, self.cache, pos,
                            free_mask, 0, live, csr, refresh)
                        if sample:
                            _, self.cache, _ = self._jit_decode_sample_dsg(
                                self.params, self.dsg, tok, self.cache,
                                pos, free_mask, 0, live, csr,
                                self._base_key, 0, temps, top_ps, refresh)
                continue
            _, self.cache = self._jit_decode_greedy(
                self.params, self.dsg, tok, self.cache, pos, free_mask, 0,
                live)
            if sample:
                _, self.cache = self._jit_decode_sample(
                    self.params, self.dsg, tok, self.cache, pos, free_mask,
                    0, live, self._base_key, 0, temps, top_ps)

    @runs_on("worker")
    def begin_step(self) -> Optional[StepPlan]:
        """Host half of a decode step: admit queued prompts, emit each
        active lane's pending token, grow page tables for this step's
        write positions, and build the decode operands.  Returns None
        when no lane is active (and raises if prompts are queued but can
        never be admitted).  Callers must follow a non-None plan with
        the jitted decode dispatch and `commit_step()` — `step()` is
        that composition; replica executors batch the middle."""
        with self.telemetry.span("repro.engine.begin"):
            return self._begin_step()

    @runs_on("worker")
    def _begin_step(self) -> Optional[StepPlan]:
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
        admits = self._admit()
        active = [i for i, s in enumerate(self.slots) if not s.free]
        if not active:
            if self.queue:
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
        # kv_cache.decode_view) and are never observed.
        donor = active[0]
        tok = np.array(self._next_tok, np.int32)
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
                if s.free:
                    free_mask[i] = True
                    tok[i] = self._next_tok[donor]
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
                r = self.slots[i].req
                if not r.output:
                    r.first_token = time.perf_counter()   # TTFT stamp
                r.output.append(int(tok[i]))
            return StepPlan(active=active, donor=donor, tok=tok, pos=pos,
                            free_mask=free_mask, temps=temps, top_ps=top_ps,
                            live_pages=self._live_pages(pos),
                            sample=bool((temps > 0).any()), admits=admits)
        # chunked: emission happens on device; commit_chunk appends.  A
        # DSG refresh-due point can only land on the last micro-step
        # (refresh_interval % chunk == 0 and lanes admit at chunk
        # boundaries) — predict it here so the dispatch picks the
        # score-collecting compile variant.  Lanes that would freeze on
        # budget/max_seq before the last micro-step never reach their due
        # token; an unpredicted EOS freeze just wastes one score read.
        refresh = False
        if self.dsg_rt is not None:
            R = self.dsg_rt.cfg.refresh_interval
            refresh = any(
                (len(self.slots[i].req.output) + C) % R == 0
                and int(emit_left[i]) >= C
                and self.max_seq - self.slots[i].pos >= C
                for i in active)
        return StepPlan(active=active, donor=donor, tok=tok, pos=pos,
                        free_mask=free_mask, temps=temps, top_ps=top_ps,
                        live_pages=self._live_pages(pos, C),
                        sample=bool((temps > 0).any()), chunk=C,
                        eos_ids=eos_ids, emit_left=emit_left,
                        refresh=refresh, admits=admits)

    @runs_on("worker")
    def commit_step(self, plan: StepPlan, next_tok: np.ndarray,
                    seconds: float) -> dict:
        """Record a decode result: latch each lane's next input token,
        account the device time/tokens, and retire finished lanes.
        `next_tok` must already be host-side (the caller syncs — that is
        where the device wait belongs in the timing).  A MoE step's
        counts follow the tokens (make_decode_fns); they are counted and
        returned as span attrs (empty for a dense model)."""
        with self.telemetry.span("repro.engine.commit") as span:
            self._next_tok = np.array(next_tok[:self.n_slots], np.int32)
            self.decode_seconds += seconds
            self.decode_tokens += len(plan.active)
            self.steps += 1
            for i in plan.active:
                self.slots[i].pos += 1
            span.set(retired=len(self._retire(plan.active)))
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
            r = slot.req
            hit_eos = r.eos_id is not None and r.output[-1] == r.eos_id
            if hit_eos or len(r.output) >= r.max_new \
                    or slot.pos >= self.max_seq:
                r.status = "ok"
                r.finished = time.perf_counter()
                self.done[r.uid] = r
                slot.req = None
                slot.pos = 0
                self.cache = self.backend.free(self.cache, i)
                retired.append(i)
        return retired

    @runs_on("worker")
    def commit_chunk(self, plan: StepPlan, blk: np.ndarray,
                     flags: np.ndarray, next_tok: np.ndarray,
                     seconds: float, *, scores=None, bound=None):
        """Record a fused decode chunk: append each lane's emitted tokens
        (a lane's flag column is a monotone prefix — once frozen it emits
        nothing more), latch pending next-step tokens, advance `steps` by
        the micro-steps that had a live lane, and retire finished lanes.
        Host bookkeeping lags a full chunk behind the device; retirement
        re-derives the freeze conditions from the appended output, which
        mirrors the device's done logic exactly (EOS == output[-1],
        len(output) >= max_new, pos >= max_seq)."""
        with self.telemetry.span("repro.engine.commit") as span:
            rt = self.dsg_rt
            if rt is not None and bound is not None:
                # one FLOP-model entry per micro-step, over the lanes still
                # live at that micro-step — keeps flop_stats comparable to a
                # chunk=1 run of the same traffic
                for k in range(flags.shape[0]):
                    live = [i for i in plan.active if flags[k, i]]
                    if live:
                        rt.record_step(live, bound)
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
            self._next_tok = np.array(next_tok, np.int32)
            self.decode_seconds += seconds
            self.decode_tokens += emitted
            self.steps += int(flags.any(axis=1).sum())
            retired = self._retire(plan.active)
            span.set(retired=len(retired))
            if rt is not None:
                for i in retired:
                    rt.reset_lane(i)
                if scores is not None:
                    R = rt.cfg.refresh_interval
                    due = [i for i in plan.active
                           if self.slots[i].req is not None
                           and len(self.slots[i].req.output) % R == 0]
                    rt.update_from_scores(scores, due)

    @runs_on("worker")
    def _dispatch_chunk(self, plan: StepPlan):
        """Device half of a fused chunk: one jitted dispatch running
        `plan.chunk` scanned decode micro-steps.  Returns the device's
        (blk, flags, next_tok) plus (scores, bound) for DSG engines."""
        args = (self.params, self.dsg, jnp.asarray(plan.tok), self.cache,
                jnp.asarray(plan.pos), jnp.asarray(plan.free_mask),
                jnp.asarray(plan.emit_left), jnp.asarray(plan.eos_ids),
                plan.live_pages)
        scores = bound = None
        if self.dsg_rt is not None:
            rt = self.dsg_rt
            bound = rt.bound()
            csr = rt.device_csr(bound)
            if plan.sample:
                blk, flags, tok_f, self.cache, scores = \
                    self._jit_chunk_sample_dsg(
                        *args, csr, self._base_key, self.steps,
                        plan.temps, plan.top_ps, plan.refresh)
            else:
                blk, flags, tok_f, self.cache, scores = \
                    self._jit_chunk_greedy_dsg(*args, csr, plan.refresh)
        elif plan.sample:
            blk, flags, tok_f, self.cache = self._jit_chunk_sample(
                *args, self._base_key, self.steps, plan.temps,
                plan.top_ps)
        else:
            blk, flags, tok_f, self.cache = self._jit_chunk_greedy(*args)
        return blk, flags, tok_f, scores, bound

    @runs_on("worker")
    def _dispatch_dsg(self, plan: StepPlan):
        """DSG-serving decode dispatch: per-lane refresh cadence (a lane
        is due when its emitted-token count crosses refresh_interval —
        depending only on the lane's own history, so streams stay
        invariant to co-scheduling and replica count), CSR operands at
        the current pow2 bound, and the FLOP-model log entry."""
        rt = self.dsg_rt
        due = [i for i in plan.active
               if len(self.slots[i].req.output)
               % rt.cfg.refresh_interval == 0]
        refresh = bool(due)
        bound = rt.bound()
        csr = rt.device_csr(bound)
        rt.record_step(plan.active, bound)
        if plan.sample:
            next_tok, self.cache, scores = self._jit_decode_sample_dsg(
                self.params, self.dsg, jnp.asarray(plan.tok)[:, None],
                self.cache, jnp.asarray(plan.pos), plan.free_mask,
                plan.donor, plan.live_pages, csr, self._base_key,
                self.steps, plan.temps, plan.top_ps, refresh)
        else:
            next_tok, self.cache, scores = self._jit_decode_greedy_dsg(
                self.params, self.dsg, jnp.asarray(plan.tok)[:, None],
                self.cache, jnp.asarray(plan.pos), plan.free_mask,
                plan.donor, plan.live_pages, csr, refresh)
        return next_tok, scores, due

    @runs_on("worker")
    def step(self):
        """One full engine step: begin (host) -> jitted decode (device)
        -> commit (host).  Replica executors that batch the device half
        across engines call the begin/commit halves directly."""
        with self.telemetry.span("repro.engine.step",
                                 step=self.steps) as span:
            plan = self.begin_step()
            span.set(lanes=len(plan.active) if plan else 0,
                     admits=plan.admits if plan else 0)
            if plan is not None:
                span.set(**self._decode(plan))

    @runs_on("worker")
    def _decode(self, plan: StepPlan) -> dict:
        """The device half of `step()` and its commit.  `decode_seconds`
        gains the dispatch and the wait for its tokens, the spans
        `repro.engine.dispatch` and `repro.engine.sync`.  Returns the
        step span's MoE attrs (commit_step)."""
        tel = self.telemetry
        if plan.chunk > 1:
            with tel.span("repro.engine.dispatch",
                          live_pages=plan.live_pages,
                          kv_blocks=self._kv_blocks(plan)) as disp:
                blk, flags, tok_f, scores, bound = self._dispatch_chunk(plan)
            with tel.span("repro.engine.sync") as sync:
                blk, flags = np.asarray(blk), np.asarray(flags)
                tok_f = np.array(tok_f, np.int32)
            self.commit_chunk(plan, blk, flags, tok_f,
                              disp.seconds + sync.seconds, scores=scores,
                              bound=bound)
            return {}
        scores = due = None
        with tel.span("repro.engine.dispatch",
                      live_pages=plan.live_pages,
                      kv_blocks=self._kv_blocks(plan)) as disp:
            # PRNG keys depend only on (engine seed, step, lane), so mixing
            # greedy-only and sampling steps never shifts the key schedule
            if self.dsg_rt is not None:
                next_tok, scores, due = self._dispatch_dsg(plan)
            elif plan.sample:
                next_tok, self.cache = self._jit_decode_sample(
                    self.params, self.dsg, jnp.asarray(plan.tok)[:, None],
                    self.cache, jnp.asarray(plan.pos), plan.free_mask,
                    plan.donor, plan.live_pages, self._base_key, self.steps,
                    plan.temps, plan.top_ps)
            else:
                next_tok, self.cache = self._jit_decode_greedy(
                    self.params, self.dsg, jnp.asarray(plan.tok)[:, None],
                    self.cache, jnp.asarray(plan.pos), plan.free_mask,
                    plan.donor, plan.live_pages)
        with tel.span("repro.engine.sync") as sync:
            next_host = np.array(next_tok, np.int32)
        attrs = self.commit_step(plan, next_host, disp.seconds + sync.seconds)
        if self.dsg_rt is not None:
            # host pattern bookkeeping lags the device step (the paged
            # page-table split): retire first, then rewrite due lanes
            # from the refresh scores (update skips inactive lanes)
            for i in plan.active:
                if self.slots[i].req is None:          # retired in commit
                    self.dsg_rt.reset_lane(i)
            if scores is not None:
                self.dsg_rt.update_from_scores(scores, due)
        return attrs

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
        0) and surfacing the partial stream."""
        slot = self.slots[i]
        req = slot.req
        if req is None:
            return None
        slot.req = None
        slot.pos = 0
        self.cache = self.backend.free(self.cache, i)
        if self.dsg_rt is not None:
            self.dsg_rt.reset_lane(i)
        return req

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
        replica serves its next request with no recompilation."""
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
