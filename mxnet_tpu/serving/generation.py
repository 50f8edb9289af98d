"""Stateful autoregressive generation: sessions, prefill/decode
cohorts, one fixed-shape jit decode step per micro-batch (ISSUE 16).

The serving plane so far is stateless one-shot inference; generation is
the workload that stresses continuous batching hardest, because every
session carries device state (its KV cache) across THOUSANDS of
micro-batches.  The design follows the compile-once/stream-many
argument the whole repo is built on (PAPERS.md: PyGraph's
capture-once/replay-many, µ-cuDNN's closed shape families):

* **one decode program, total**: the decode step is a single jitted
  function over the whole ``[slots, max_len]`` KV arena —
  ``(params, arena, tokens[S], pos[S]) -> (logits[S, V], arena')`` —
  whose shapes never depend on how many sessions are active.  Every
  micro-batch is ONE dispatch serving ALL active slots; after
  :meth:`GenerationEngine.warm` there are zero decode-step compiles
  (test-pinned), so dispatches/token <= 1.
* **prefill cohorts**: pending prompts coalesce through the batcher's
  anchor/join machinery (:class:`~.batcher.CohortQueue`, the PR 10
  admission idiom extracted for reuse): anchor on the OLDEST pending
  session, join arrivals whose prompt falls in the same length bucket,
  pad to the bucket ladder, one prefill dispatch per cohort.  Prefill
  and decode interleave on the engine loop, so a long prompt never
  starves streaming sessions for more than one prefill dispatch.
* **paged KV admission** (kv_cache.py): ``start_session`` leases a
  decode slot and charges the session's page reservation to the PR 13
  resource ``LEDGER`` — a full pool sheds typed
  ``ServingOverloadError``; release at session end/evict is provably
  leak-free (chaos-asserted).
* **prefix reuse** (kv_cache.py): a content-hash LRU of page-aligned
  prompt-prefix activations; a hit seeds the slot's arena rows from
  the cache and the un-hit tail streams through the decode step
  (chunked prefill with chunk = 1), so shared prompt heads are
  computed once per (model, version).
* **observability**: each session rides a PR 12 trace context (kind
  ``"generation"``) whose per-token stages decompose a slow token
  (``decode_wait`` / ``decode_step`` / ``sample`` / ``deliver``); the
  PR 14 output-health guard screens every sampled logits row — a
  non-finite row fails THAT session typed (:class:`NonFiniteError`),
  cohort siblings keep streaming; ``mxnet_generation_*`` telemetry
  families ride the registry collector.

Sampling happens on HOST, per session (greedy argmax or a seeded
``np.random.Generator``), which is what makes a batched decode run
bitwise-identical to an unbatched single-session reference: the jitted
step computes each slot row independently, and the sampler consumes
exactly the same logits bytes and RNG stream either way.
"""
from __future__ import annotations

import collections
import itertools
import logging
import queue as _queue_mod
import threading
import time
import weakref

import numpy as np

from ..base import MXNetError, NonFiniteError
from ..chaos.failpoints import failpoint as _failpoint
from ..telemetry import flight as _flight
from ..telemetry import numerics as _numerics
from ..telemetry import trace as _trace
from .batcher import (CohortQueue, RequestTimeoutError, ServingClosedError,
                      ServingWorkerError)
from .kv_cache import KVSlotPool, PrefixCache
from .metrics import ServingMetrics

log = logging.getLogger("mxnet_tpu.serving")

_session_seq = itertools.count(1)

# all live engines, for module-level stats() + the telemetry collector
_ENGINES = weakref.WeakValueDictionary()
_ENGINES_LOCK = threading.Lock()


# -- model contract -----------------------------------------------------------
class GenerationModel:
    """The pure-function contract a generation engine drives.

    ``prefill_fn(params, tokens[B, L], mask[B, L]) -> (kv, logits)``
        causal self-attention over a padded prompt cohort; ``kv`` is a
        dict of ``[B, L, ...]`` arrays (the rows written into the
        arena), ``logits`` is ``[B, L, vocab]`` (the engine reads the
        last REAL position per row).
    ``decode_fn(params, arena, tokens[S], pos[S]) -> (logits, arena')``
        one token per slot: write this token's k/v at ``pos``, attend
        over the arena masked to ``<= pos``, return ``[S, vocab]``
        logits and the functionally-updated arena.
    ``init_arena_fn(slots, max_len) -> arena``
        dict of zeroed ``[slots, max_len, ...]`` arrays, one per KV
        tensor (multi-layer models use one pair per layer).

    ``jit=True`` wraps both functions in ``jax.jit`` (the serving
    configuration); ``jit=False`` runs them as plain host callables —
    the CPU-only configuration the tests' per-token-cost engines use,
    so the machinery gates never depend on device timing.
    """

    def __init__(self, params, prefill_fn, decode_fn, init_arena_fn,
                 vocab, max_len, jit=True, eos_id=None):
        self.params = params
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.init_arena_fn = init_arena_fn
        self.vocab = int(vocab)
        self.max_len = int(max_len)
        self.jit = bool(jit)
        self.eos_id = eos_id

    def bytes_per_token(self):
        """Ledger page costing: KV bytes one slot commits per token."""
        probe = self.init_arena_fn(1, 1)
        return int(sum(np.asarray(a).dtype.itemsize
                       * int(np.prod(np.asarray(a).shape[2:] or (1,)))
                       for a in probe.values()))


def _np_softmax(x):
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)


def tiny_lm(vocab=32, d_model=16, max_len=256, seed=0, jit=True,
            eos_id=None, per_token_cost_s=0.0):
    """A deterministic single-layer-attention LM for tests and smokes.
    ``jit=True`` builds jax functions (the serving config);
    ``jit=False`` builds numpy twins — same math, pure host — plus an
    optional ``per_token_cost_s`` sleep so a test can model a fixed
    per-token device cost without any device in the loop."""
    rng = np.random.RandomState(seed)
    scale = 1.0 / np.sqrt(d_model)
    params = {
        "emb": (rng.randn(vocab, d_model) * 0.5).astype(np.float32),
        "pos": (rng.randn(max_len, d_model) * 0.1).astype(np.float32),
        "wq": (rng.randn(d_model, d_model) * scale).astype(np.float32),
        "wk": (rng.randn(d_model, d_model) * scale).astype(np.float32),
        "wv": (rng.randn(d_model, d_model) * scale).astype(np.float32),
        "wo": (rng.randn(d_model, d_model) * scale).astype(np.float32),
        "w_out": (rng.randn(d_model, vocab) * scale).astype(np.float32),
    }

    if jit:
        import jax.numpy as jnp

        # resolve the optional fused prefill attention ONCE, at model
        # build (host side): the traced prefill body below must contain
        # zero lookups/metrics.  None (MXNET_KERNELS=off) keeps the
        # einsum path.  Right-padded cohorts make the causal mask
        # subsume the key-padding mask at every consumed query row, so
        # the flash kernel is drop-in for the rows the engine reads.
        from .. import kernels as _kernels
        attn_kernel = _kernels.get("attention", (1, 1, max_len, d_model),
                                   np.float32)
        attn_scale = float(scale)   # static kernel param, host-resolved

        def prefill_fn(p, tokens, mask):
            L = tokens.shape[1]
            x = p["emb"][tokens] + p["pos"][:L][None, :, :]
            q = x @ p["wq"]
            k = x @ p["wk"]
            v = x @ p["wv"]
            if attn_kernel is not None:
                y = attn_kernel(q[:, None], k[:, None], v[:, None],
                                causal=True, sm_scale=attn_scale)[:, 0]
            else:
                att = jnp.einsum("bid,bjd->bij", q, k) * scale
                allowed = (jnp.arange(L)[None, :, None]
                           >= jnp.arange(L)[None, None, :]) \
                    & (mask[:, None, :] > 0)
                att = jnp.where(allowed, att, -jnp.inf)
                att = att - att.max(axis=-1, keepdims=True)
                w = jnp.exp(att)
                w = jnp.where(allowed, w, 0.0)
                w = w / w.sum(axis=-1, keepdims=True)
                y = jnp.einsum("bij,bjd->bid", w, v)
            h = x + y @ p["wo"]
            return {"k": k, "v": v}, h @ p["w_out"]

        def decode_fn(p, arena, tokens, pos):
            S, Lmax = arena["k"].shape[:2]
            x = p["emb"][tokens] + p["pos"][pos]
            q = x @ p["wq"]
            k_new = x @ p["wk"]
            v_new = x @ p["wv"]
            rows = jnp.arange(S)
            k_arena = arena["k"].at[rows, pos].set(k_new)
            v_arena = arena["v"].at[rows, pos].set(v_new)
            att = jnp.einsum("sd,sld->sl", q, k_arena) * scale
            allowed = jnp.arange(Lmax)[None, :] <= pos[:, None]
            att = jnp.where(allowed, att, -jnp.inf)
            att = att - att.max(axis=-1, keepdims=True)
            w = jnp.exp(att)
            w = jnp.where(allowed, w, 0.0)
            w = w / w.sum(axis=-1, keepdims=True)
            y = jnp.einsum("sl,sld->sd", w, v_arena)
            h = x + y @ p["wo"]
            return h @ p["w_out"], {"k": k_arena, "v": v_arena}

        def init_arena_fn(slots, L):
            return {"k": jnp.zeros((slots, L, d_model), jnp.float32),
                    "v": jnp.zeros((slots, L, d_model), jnp.float32)}
    else:
        def prefill_fn(p, tokens, mask):
            if per_token_cost_s:
                time.sleep(per_token_cost_s * tokens.shape[1])
            L = tokens.shape[1]
            x = p["emb"][tokens] + p["pos"][:L][None, :, :]
            q = x @ p["wq"]
            k = x @ p["wk"]
            v = x @ p["wv"]
            att = np.einsum("bid,bjd->bij", q, k) * scale
            allowed = (np.arange(L)[None, :, None]
                       >= np.arange(L)[None, None, :]) \
                & (mask[:, None, :] > 0)
            att = np.where(allowed, att, -np.inf)
            att = att - att.max(axis=-1, keepdims=True)
            w = np.exp(att)
            w = np.where(allowed, w, 0.0)
            w = w / w.sum(axis=-1, keepdims=True)
            y = np.einsum("bij,bjd->bid", w, v)
            h = x + y @ p["wo"]
            return {"k": k, "v": v}, h @ p["w_out"]

        def decode_fn(p, arena, tokens, pos):
            if per_token_cost_s:
                time.sleep(per_token_cost_s)
            S, Lmax = arena["k"].shape[:2]
            x = p["emb"][tokens] + p["pos"][pos]
            q = x @ p["wq"]
            rows = np.arange(S)
            k_arena = np.array(arena["k"])
            v_arena = np.array(arena["v"])
            k_arena[rows, pos] = x @ p["wk"]
            v_arena[rows, pos] = x @ p["wv"]
            att = np.einsum("sd,sld->sl", q, k_arena) * scale
            allowed = np.arange(Lmax)[None, :] <= pos[:, None]
            att = np.where(allowed, att, -np.inf)
            att = att - att.max(axis=-1, keepdims=True)
            w = np.exp(att)
            w = np.where(allowed, w, 0.0)
            w = w / w.sum(axis=-1, keepdims=True)
            y = np.einsum("sl,sld->sd", w, v_arena)
            h = x + y @ p["wo"]
            return h @ p["w_out"], {"k": k_arena, "v": v_arena}

        def init_arena_fn(slots, L):
            return {"k": np.zeros((slots, L, d_model), np.float32),
                    "v": np.zeros((slots, L, d_model), np.float32)}

    return GenerationModel(params, prefill_fn, decode_fn, init_arena_fn,
                           vocab=vocab, max_len=max_len, jit=jit,
                           eos_id=eos_id)


# -- session ------------------------------------------------------------------
class GenerationSession:
    """One streaming generation request: iterate it for tokens as they
    decode, or block on :meth:`result` for the full list.  Failures are
    TYPED — the iterator/``result`` raise the structured error the
    engine failed the session with (never a hang: every wait is
    bounded)."""

    PENDING, ACTIVE, DONE, FAILED = "pending", "active", "done", "failed"

    def __init__(self, engine, prompt, max_new_tokens, greedy, seed,
                 slot, version, trace):
        self.session_id = f"{engine.name}#{next(_session_seq)}"
        self.engine = engine
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new_tokens = int(max_new_tokens)
        self.greedy = bool(greedy)
        self.rng = (None if greedy
                    else np.random.Generator(np.random.PCG64(int(seed))))
        self.slot = slot
        self.version = version
        self.trace = trace
        self.state = self.PENDING
        self.pos = 0                       # next arena write position
        self.pending = collections.deque()  # prompt tail fed via decode
        self.tokens = []                   # generated tokens, in order
        self.error = None
        self.t_enqueue = time.perf_counter()
        self.t_last_emit = None
        self._out = _queue_mod.Queue()
        self._done = threading.Event()
        self._cancelled = False

    # -- engine side ---------------------------------------------------------
    def _emit(self, token):
        now = time.perf_counter()
        if self.t_last_emit is not None:
            self.engine.metrics.observe(
                "intertoken_ms", (now - self.t_last_emit) * 1e3)
        self.t_last_emit = now
        self.tokens.append(int(token))
        self.trace.add_stage("deliver", now, time.perf_counter())
        self._out.put(("tok", int(token)))

    def _finish(self, state, error=None):
        if self._done.is_set():
            return
        self.state = state
        self.error = error
        self.engine._release_session(self)
        if error is not None:
            self.trace.event("failed", error=type(error).__name__)
            self._out.put(("err", error))
        else:
            self._out.put(("end", None))
        self.trace.finish(status="ok" if error is None else "error")
        self._done.set()

    # -- client side ---------------------------------------------------------
    def __iter__(self):
        yielded = 0
        while True:
            try:
                kind, payload = self._out.get(
                    timeout=self.engine.session_timeout_s)
            except _queue_mod.Empty:
                waited = (time.perf_counter() - self.t_enqueue) * 1e3
                raise RequestTimeoutError(
                    self.engine.name, waited,
                    self.engine.session_timeout_s * 1e3) from None
            if kind == "tok":
                yielded += 1
                yield payload
            elif kind == "err":
                raise payload
            else:
                return

    def result(self, timeout=None):
        """Block for the complete generation; returns the token list."""
        timeout = (self.engine.session_timeout_s if timeout is None
                   else timeout)
        if not self._done.wait(timeout):
            waited = (time.perf_counter() - self.t_enqueue) * 1e3
            raise RequestTimeoutError(self.engine.name, waited,
                                      timeout * 1e3)
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    def cancel(self):
        """Ask the engine to drop this session at the next tick; the
        slot and its pages release there (or immediately if the session
        never reached the loop)."""
        self._cancelled = True

    def done(self):
        return self._done.is_set()


# -- engine -------------------------------------------------------------------
class GenerationEngine:
    """Prefill/decode loop over a fixed slot arena (the tentpole).

    One background thread interleaves (a) prefill cohorts formed by
    anchor/join over the pending queue and (b) ONE decode dispatch per
    tick covering every active slot.  The loop has a restart budget
    (like the batcher's worker budget): a crash fails the ACTIVE
    sessions typed-retryable (they can resume on a sibling engine —
    the chaos scenario's contract) and restarts the loop; an exhausted
    budget fails the engine fast, releasing every slot and page."""

    def __init__(self, model, name="generator", slots=None,
                 page_tokens=None, kv_budget_mb=None,
                 prefix_cache_entries=None, max_len=None,
                 prefill_max_batch=4, session_timeout_s=60.0,
                 loop_restarts=None, metrics=None, version=1):
        from .. import config as _config
        self.name = str(name)
        self.model = model
        self.slots = int(slots if slots is not None
                         else _config.get("MXNET_GENERATION_SLOTS"))
        self.max_len = int(max_len if max_len is not None
                           else min(model.max_len,
                                    _config.get("MXNET_GENERATION_MAX_LEN")))
        page_tokens = int(page_tokens if page_tokens is not None
                          else _config.get("MXNET_GENERATION_PAGE_TOKENS"))
        budget_mb = (kv_budget_mb if kv_budget_mb is not None
                     else _config.get("MXNET_GENERATION_KV_BUDGET_MB"))
        prefix_entries = int(
            prefix_cache_entries if prefix_cache_entries is not None
            else _config.get("MXNET_GENERATION_PREFIX_CACHE"))
        self.prefill_max_batch = int(prefill_max_batch)
        self.session_timeout_s = float(session_timeout_s)
        self._restart_budget = int(
            loop_restarts if loop_restarts is not None
            else _config.get("MXNET_GENERATION_LOOP_RESTARTS"))
        self.metrics = metrics or ServingMetrics(self.name)
        self.pool = KVSlotPool(
            f"generation/{self.name}", self.slots, page_tokens,
            model.bytes_per_token(), int(budget_mb) * (1 << 20))
        self.prefix_cache = PrefixCache(
            f"generation/{self.name}", prefix_entries, page_tokens)

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._versions = {int(version): model}
        self._version = int(version)
        self._prefill_fns = {}   # version -> fn
        self._decode_fns = {}    # version -> fn
        self._arena = model.init_arena_fn(self.slots, self.max_len)
        # prompt-length ladder: powers of two, page-aligned tail
        self._prompt_ladder = []
        b = 8
        while b < self.max_len:
            self._prompt_ladder.append(b)
            b *= 2
        self._prompt_ladder.append(self.max_len)

        # anchor/join prefill admission (PR 10 machinery, extracted)
        self._pending = CohortQueue(
            lambda s: self._prompt_bucket(len(s.prompt) - s.pos),
            self.prefill_max_batch)
        self._active = {}        # slot index -> session
        self._closed = False
        self._failed = False
        # compile accounting: the counters increment inside the traced
        # function bodies, so they move ONLY when XLA (re)traces — the
        # "0 decode-step compiles post-warm" acceptance pin reads them
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self.decode_steps = 0
        self.tokens_emitted = 0
        self.sessions_started = 0
        self.sessions_failed = 0
        self.max_active = 0
        self._build_fns(self._version)
        self._thread = threading.Thread(
            target=self._loop_forever, daemon=True,
            name=f"generation-{self.name}")
        self._thread.start()
        with _ENGINES_LOCK:
            _ENGINES[self.name] = self
        _register_collector()

    # -- shape ladder --------------------------------------------------------
    def _prompt_bucket(self, n):
        for b in self._prompt_ladder:
            if n <= b:
                return b
        return self._prompt_ladder[-1]

    # -- per-version compiled functions --------------------------------------
    def _build_fns(self, version):
        with self._lock:
            model = self._versions[version]
        if not model.jit:
            with self._lock:
                self._prefill_fns[version] = self._host_prefill(model)
                self._decode_fns[version] = model.decode_fn
            return
        import jax

        def prefill_step(params, arena, tokens, mask, slot_rows):
            self.prefill_compiles += 1   # moves at trace time only
            kv, logits = model.prefill_fn(params, tokens, mask)
            L = tokens.shape[1]
            # padding cohort rows carry slot_rows == slots (out of
            # bounds): mode="drop" discards their junk k/v instead of
            # scattering it over a live session's slot
            for tname in arena:
                arena[tname] = arena[tname].at[slot_rows, :L].set(
                    kv[tname], mode="drop")
            return arena, logits, kv

        def decode_step(params, arena, tokens, pos):
            self.decode_compiles += 1    # moves at trace time only
            return model.decode_fn(params, arena, tokens, pos)

        with self._lock:   # jax.jit wrapping is lazy: no compile held here
            self._prefill_fns[version] = jax.jit(prefill_step)
            self._decode_fns[version] = jax.jit(decode_step)

    @staticmethod
    def _host_prefill(model):
        def prefill_step(params, arena, tokens, mask, slot_rows):
            kv, logits = model.prefill_fn(params, tokens, mask)
            L = tokens.shape[1]
            real = slot_rows < next(iter(arena.values())).shape[0]
            for tname in arena:
                arena[tname][slot_rows[real], :L] = kv[tname][real]
            return arena, logits, kv
        return prefill_step

    # -- warmup (PR 7 idiom: compile the ladder before traffic) --------------
    def warm(self, version=None):
        """AOT-compile the decode step and every prefill prompt bucket
        for ``version`` (default: latest).  Returns the warmed bucket
        list; after this, steady-state decode performs ZERO compiles —
        ``stats()['decode_compiles']`` is the pin."""
        with self._lock:
            version = self._version if version is None else int(version)
            model = self._versions[version]
            decode_fn = self._decode_fns[version]
            prefill_fn = self._prefill_fns[version]
        B = self.prefill_max_batch
        arena = model.init_arena_fn(self.slots, self.max_len)
        tokens = np.zeros(self.slots, np.int32)
        pos = np.zeros(self.slots, np.int32)
        params = model.params
        decode_fn(params, arena, tokens, pos)
        warmed = []
        for bucket in self._prompt_ladder:
            if bucket > self.max_len:
                continue
            ptoks = np.zeros((B, bucket), np.int32)
            # padding rows keep position 0 unmasked so the row softmax
            # normalizer never sees an all-masked (NaN) row
            mask = np.zeros((B, bucket), np.float32)
            mask[:, 0] = 1.0
            rows = np.full(B, self.slots, np.int32)  # all padding
            prefill_fn(params, arena, ptoks, mask, rows)
            warmed.append(bucket)
        _flight.record("serving", "generation_warm", engine=self.name,
                       version=version, buckets=len(warmed))
        return warmed

    # -- hot reload ----------------------------------------------------------
    def load(self, model, version=None, warm=True):
        """Hot-reload: build + AOT-warm the new version's functions
        BEFORE the served-version pointer flips (the PR 7
        warm-before-flip contract), then flip and retire the stale
        version's ladders + prefix-cache activations.  In-flight
        sessions keep streaming; their next decode step serves the new
        version (per-micro-batch resolution, like the batcher), their
        KV computed under the old version stays — the standard
        mid-stream reload semantics."""
        with self._lock:
            new_version = (self._version + 1 if version is None
                           else int(version))
            prev = self._version
            self._versions[new_version] = model
        self._build_fns(new_version)
        if warm:
            self.warm(new_version)
        with self._lock:
            self._version = new_version
        self.retire_stale({new_version, prev})
        _flight.record("serving", "generation_flip", engine=self.name,
                       version=new_version, prev=prev)
        return new_version

    def retire_stale(self, keep_versions):
        """Drop per-version decode/prefill ladders and prefix-cache
        activations for every version not in ``keep_versions`` (the
        ISSUE 16 small fix: a stale version's compiled ladder or cached
        activations must never serve after a flip)."""
        keep = {int(v) for v in keep_versions}
        with self._lock:
            doomed = [v for v in self._versions
                      if v not in keep and v != self._version]
            for v in doomed:
                self._versions.pop(v, None)
                self._prefill_fns.pop(v, None)
                self._decode_fns.pop(v, None)
        model = self.name.rsplit("/", 1)[-1]
        self.prefix_cache.evict_stale_versions(model, keep)
        return len(doomed)

    # -- admission -----------------------------------------------------------
    def start_session(self, prompt, max_new_tokens=16, greedy=True,
                      seed=0):
        """Admit one session: validates the prompt, leases a slot +
        charges the full page reservation (prompt + max_new tokens) to
        the ledger — sheds typed when the pool/budget cannot hold it —
        and queues the session for the next prefill cohort."""
        if self._closed or self._failed:
            raise ServingClosedError(self.name)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise MXNetError(f"generation[{self.name}]: empty prompt")
        with self._lock:
            version = self._version
            model = self._versions[version]
        if prompt.size + int(max_new_tokens) > self.max_len:
            raise MXNetError(
                f"generation[{self.name}]: prompt ({prompt.size}) + "
                f"max_new_tokens ({max_new_tokens}) exceeds max_len "
                f"{self.max_len}")
        if int(prompt.max()) >= model.vocab or int(prompt.min()) < 0:
            raise MXNetError(
                f"generation[{self.name}]: prompt token out of range "
                f"[0, {model.vocab})")
        tr = _trace.start("generation", self.name)
        try:
            with tr.stage("admit"):
                slot = self.pool.acquire(
                    f"s{next(_session_seq)}",
                    prompt.size + int(max_new_tokens))
        except BaseException as e:
            # shed (pool exhausted / page budget): the trace still
            # finishes, typed — rejected admissions are traceable too,
            # and the span must not leak into the tracer's active set
            try:
                tr.event("rejected", error=type(e).__name__)
            finally:
                tr.finish(status="rejected")
            raise
        sess = GenerationSession(self, prompt, max_new_tokens, greedy,
                                 seed, slot, version, tr)
        with self._lock:
            self.sessions_started += 1
        self.metrics.incr("sessions_total")
        self._pending.put(sess)
        with self._cond:
            self._cond.notify_all()
        return sess

    def generate(self, prompt, **kw):
        """Blocking convenience: the full token list."""
        return self.start_session(prompt, **kw).result()  # graftlint: disable=unbounded-wait -- result() defaults its wait to engine.session_timeout_s and raises typed RequestTimeoutError

    # -- the loop ------------------------------------------------------------
    def _loop_forever(self):
        restarts_left = self._restart_budget
        while True:
            try:
                self._loop()
                return
            except Exception as e:  # noqa: BLE001 — typed fan-out below
                if self._closed:
                    return
                failed = self._fail_active(e)
                with self._lock:
                    self.sessions_failed += failed
                _flight.record("serving", "generation_loop_crash",
                               severity="error", engine=self.name,
                               error=type(e).__name__,
                               restarts_left=restarts_left)
                if restarts_left <= 0:
                    self._fail_engine(e)
                    return
                restarts_left -= 1
                log.exception(
                    "generation[%s]: loop crashed (%s); restarting "
                    "(%d restart(s) left)", self.name,
                    type(e).__name__, restarts_left)

    def _loop(self):
        while not self._closed:
            progressed = self._prefill_tick()
            progressed = self._decode_tick() or progressed
            if not progressed:
                with self._cond:
                    if (self._closed or self._active
                            or len(self._pending)):
                        continue
                    self._cond.wait(0.005)

    # -- prefill -------------------------------------------------------------
    def _prefill_tick(self):
        cohort = self._pending.take(timeout=0.0)
        cohort = [s for s in cohort if not self._drop_if_cancelled(s)]
        if not cohort:
            return False
        with self._lock:
            version = self._version
            model = self._versions[version]
            prefill_fn = self._prefill_fns[version]
        mname = self.name.rsplit("/", 1)[-1]

        # prefix-cache pass: a hit seeds the arena rows from cached
        # activations; the remaining tail streams through decode steps
        need_prefill = []
        for sess in cohort:
            with sess.trace.stage("prefix_lookup"):
                hit_len, kv = self.prefix_cache.lookup(
                    mname, version, sess.prompt)
            if hit_len:
                self._write_prefix(sess.slot.index, kv, model)
                sess.pos = hit_len
                sess.pending.extend(sess.prompt[hit_len:].tolist())
                sess.trace.event("prefix_hit", tokens=hit_len)
                self.metrics.incr("prefix_hits")
                self._activate(sess)
            else:
                self.metrics.incr("prefix_misses")
                need_prefill.append(sess)
        if not need_prefill:
            return True

        bucket = max(self._prompt_bucket(len(s.prompt))
                     for s in need_prefill)
        B = self.prefill_max_batch
        tokens = np.zeros((B, bucket), np.int32)
        mask = np.zeros((B, bucket), np.float32)
        mask[:, 0] = 1.0  # padding rows: see warm()
        rows = np.full(B, self.slots, np.int32)  # padding -> dropped
        for i, sess in enumerate(need_prefill):
            L = len(sess.prompt)
            tokens[i, :L] = sess.prompt
            mask[i] = 0.0
            mask[i, :L] = 1.0
            rows[i] = sess.slot.index
        t0 = time.perf_counter()
        self._arena, logits, kv = prefill_fn(  # graftlint: disable=lock-discipline -- loop-thread-owned device state: only the serve loop touches the arena after start(); holding the lock across a device dispatch would serialize admission with prefill
            model.params, self._arena, tokens, mask, rows)
        logits_host = np.asarray(logits)
        t1 = time.perf_counter()
        for i, sess in enumerate(need_prefill):
            sess.trace.add_stage("prefill", t0, t1)
            L = len(sess.prompt)
            sess.pos = L
            if self.prefix_cache.enabled():
                host_kv = {tname: np.asarray(kv[tname][i])
                           for tname in kv}
                stored = self.prefix_cache.store(
                    mname, version, sess.prompt, host_kv)
                if stored:
                    sess.trace.event("prefix_store", tokens=stored)
            row = logits_host[i, L - 1]
            self._activate(sess, until=t0)
            self._consume_logits(sess, row)
        self.metrics.observe_batch(len(need_prefill), B)
        return True

    def _write_prefix(self, slot_index, kv, model):
        """Seed one slot's arena rows from cached host activations."""
        if model.jit:
            for tname, host in kv.items():
                self._arena[tname] = self._arena[tname] \
                    .at[slot_index, :host.shape[0]].set(host)  # graftlint: disable=lock-discipline -- loop-thread-owned device state (see _prefill_tick)
        else:
            for tname, host in kv.items():
                self._arena[tname][slot_index, :host.shape[0]] = host  # graftlint: disable=lock-discipline -- loop-thread-owned device state (see _prefill_tick)

    def _activate(self, sess, until=None):
        sess.state = GenerationSession.ACTIVE
        now = time.perf_counter()
        with self._lock:
            self._active[sess.slot.index] = sess
            self.max_active = max(self.max_active, len(self._active))
            self.metrics.gauge("sessions_active", len(self._active))
        sess.trace.add_stage("prefill_wait", sess.t_enqueue,
                             now if until is None else until)
        sess.t_mark = now

    # -- decode --------------------------------------------------------------
    def _decode_tick(self):
        with self._lock:
            active = dict(self._active)
            version = self._version
            model = self._versions[version]
            decode_fn = self._decode_fns[version]
        if not active:
            return False
        for sess in list(active.values()):
            if self._drop_if_cancelled(sess):
                active.pop(sess.slot.index, None)
        if not active:
            return True
        tokens = np.zeros(self.slots, np.int32)
        pos = np.zeros(self.slots, np.int32)
        feeding = {}   # slot index -> ("tail"|"gen", session)
        for idx, sess in active.items():
            if sess.pending:
                tokens[idx] = sess.pending.popleft()
                feeding[idx] = ("tail", sess)
            else:
                tokens[idx] = (sess.tokens[-1] if sess.tokens
                               else int(sess.prompt[-1]))
                feeding[idx] = ("gen", sess)
            pos[idx] = sess.pos
        _failpoint("serving/generation/decode")
        t0 = time.perf_counter()
        logits, self._arena = decode_fn(model.params, self._arena,  # graftlint: disable=lock-discipline -- loop-thread-owned device state (see _prefill_tick)
                                        tokens, pos)
        logits_host = np.asarray(logits)
        t1 = time.perf_counter()
        self.decode_steps += 1
        self.metrics.incr("decode_steps")
        # PR 14 output-health guard, generalized to per-step logits:
        # a non-finite row fails THAT session typed, siblings stream on
        bad = set(_numerics.guard_rows([logits_host], self.slots))
        for idx, (mode, sess) in feeding.items():
            sess.trace.add_stage("decode_wait",
                                 getattr(sess, "t_mark", t0), t0)
            sess.trace.add_stage("decode_step", t0, t1)
            sess.t_mark = t1
            sess.pos += 1
            if sess.pending:
                continue   # mid-tail: logits are internal, not served
            if idx in bad:
                with self._lock:
                    self.sessions_failed += 1
                _numerics.record_serving_nonfinite(self.name, 1)
                sess._finish(GenerationSession.FAILED, NonFiniteError(
                    f"generation[{self.name}] session "
                    f"{sess.session_id}", stat="logits",
                    value="nan/inf",
                    detail="non-finite decode logits; the session "
                           "failed typed, cohort siblings keep "
                           "streaming (docs/serving.md)"))
                continue
            self._consume_logits(sess, logits_host[idx])
        return True

    def _consume_logits(self, sess, row):
        """Sample the next token from one served logits row (host-side,
        per-session RNG), emit it, and finish the session at
        max_new_tokens/EOS."""
        if not np.isfinite(row).all():
            with self._lock:
                self.sessions_failed += 1
            _numerics.record_serving_nonfinite(self.name, 1)
            sess._finish(GenerationSession.FAILED, NonFiniteError(
                f"generation[{self.name}] session {sess.session_id}",
                stat="logits", value="nan/inf",
                detail="non-finite prefill logits"))
            return
        t0 = time.perf_counter()
        if sess.greedy:
            token = int(np.argmax(row))
        else:
            probs = _np_softmax(row.astype(np.float64))
            token = int(sess.rng.choice(row.shape[0], p=probs))
        sess.trace.add_stage("sample", t0, time.perf_counter())
        self.tokens_emitted += 1
        self.metrics.incr("tokens_total")
        sess._emit(token)
        with self._lock:
            model = self._versions[self._version]
        if (len(sess.tokens) >= sess.max_new_tokens
                or (model.eos_id is not None and token == model.eos_id)):
            sess._finish(GenerationSession.DONE)

    # -- failure fan-out / lifecycle -----------------------------------------
    def _drop_if_cancelled(self, sess):
        if sess._cancelled and not sess.done():
            sess._finish(GenerationSession.FAILED,
                         ServingClosedError(self.name))
            return True
        return False

    def _release_session(self, sess):
        self.pool.release(sess.slot)
        with self._cond:
            self._active.pop(sess.slot.index, None)
            self.metrics.gauge("sessions_active", len(self._active))
            self._cond.notify_all()

    def _fail_active(self, cause, exhausted=False):
        """Crash fan-out: every admitted session fails typed-retryable
        (``ServingWorkerError`` — the client resumes on a sibling
        engine with ``prompt + tokens`` as the new prompt, which the
        sibling's prefix cache makes cheap) and provably releases its
        slot and pages."""
        with self._lock:
            doomed = list(self._active.values())
        doomed += self._pending.drain()
        for sess in doomed:
            if not sess.done():
                err = (cause if isinstance(cause, ServingClosedError)
                       else ServingWorkerError(self.name, cause=cause,
                                               exhausted=exhausted))
                sess._finish(GenerationSession.FAILED, err)
        return len(doomed)

    def _fail_engine(self, cause):
        self._failed = True
        failed = self._fail_active(cause, exhausted=True)
        with self._lock:
            self.sessions_failed += failed
        log.error("generation[%s]: loop restart budget exhausted; "
                  "engine failed fast (%s: %s)", self.name,
                  type(cause).__name__, cause)

    def close(self, timeout=10.0):
        """Stop the loop and fail anything still queued/active typed;
        idempotent.  Every slot and ledger page releases."""
        if self._closed:
            return
        self._closed = True
        with self._cond:
            self._cond.notify_all()
        self._thread.join(timeout)
        self._fail_active(ServingClosedError(self.name))
        self.prefix_cache.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- observability -------------------------------------------------------
    def stats(self):
        with self._lock:
            active = len(self._active)
            version = self._version
            versions = sorted(self._versions)
            started = self.sessions_started
            failed = self.sessions_failed
            max_active = self.max_active
        return {
            "engine": self.name, "version": version,
            "versions_resident": versions,
            "sessions_active": active,
            "sessions_pending": len(self._pending),
            "sessions_started": started,
            "sessions_failed": failed,
            "max_active": max_active,
            "tokens_emitted": self.tokens_emitted,
            "decode_steps": self.decode_steps,
            "decode_compiles": self.decode_compiles,
            "prefill_compiles": self.prefill_compiles,
            "failed": self._failed, "closed": self._closed,
            "kv": self.pool.stats(),
            "prefix_cache": self.prefix_cache.stats(),
        }


# -- module-level stats + telemetry collector ---------------------------------
def stats():
    """{engine name: stats dict} for every live engine — the payload
    behind ``telemetry.snapshot()['generation']``."""
    with _ENGINES_LOCK:
        engines = list(_ENGINES.values())
    return {e.name: e.stats() for e in engines}


def _generation_samples():
    gauges = {
        "sessions_active": ("mxnet_generation_sessions_active",
                            "active generation sessions (decode slots "
                            "streaming), by engine"),
        "decode_compiles": ("mxnet_generation_decode_compiles",
                            "decode-step XLA traces — flat after warm "
                            "or the ladder regressed"),
        "max_active": ("mxnet_generation_max_active",
                       "high-water concurrent sessions in one decode "
                       "micro-batch"),
    }
    counters = {
        "sessions_started": ("mxnet_generation_sessions_total",
                             "admitted generation sessions, by engine"),
        "sessions_failed": ("mxnet_generation_sessions_failed_total",
                            "sessions failed typed (guard, crash, "
                            "shed), by engine"),
        "tokens_emitted": ("mxnet_generation_tokens_total",
                           "tokens sampled and streamed, by engine"),
        "decode_steps": ("mxnet_generation_decode_steps_total",
                         "fixed-shape decode dispatches, by engine"),
    }
    out = []
    for name, snap in sorted(stats().items()):
        labels = {"engine": name}
        for field, (fam, help_) in gauges.items():
            out.append((fam, "gauge", help_, labels, snap[field]))
        for field, (fam, help_) in counters.items():
            out.append((fam, "counter", help_, labels, snap[field]))
        kv = snap["kv"]
        out.append(("mxnet_generation_kv_pages", "gauge",
                    "KV-cache pages committed to live sessions",
                    labels, kv["pages_in_use"]))
        out.append(("mxnet_generation_kv_bytes", "gauge",
                    "KV-cache bytes committed to live sessions "
                    "(mirrors the resource ledger's kv_pages rows)",
                    labels, kv["kv_bytes"]))
        out.append(("mxnet_generation_sheds_total", "counter",
                    "sessions shed typed at admission (pool full / "
                    "budget)", labels, kv["sheds"]))
        pc = snap["prefix_cache"]
        out.append(("mxnet_generation_prefix_hits_total", "counter",
                    "prefix-cache hits (prompt heads served from "
                    "cached activations)", labels, pc["hits"]))
        out.append(("mxnet_generation_prefix_misses_total", "counter",
                    "prefix-cache misses (full prefill paid)",
                    labels, pc["misses"]))
    return out


_collector_registered = False


def _register_collector():
    global _collector_registered
    if _collector_registered:
        return
    from .. import telemetry as _telemetry
    _telemetry.register_collector("generation", stats,
                                  _generation_samples)
    _collector_registered = True
