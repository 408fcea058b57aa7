"""Batched serving engine: slot-based continuous batching over the decode step.

Each of ``max_batch`` slots holds one request's KV/state cache (leading slot
axis via vmap, so every slot advances with its own position counter — slots
are never forced into lockstep).  Prefill runs per request (B=1) and the
resulting cache row is written into a free slot; a single jitted vmapped
decode wave then advances all active slots together.

AutoChunk integration: pass ``autochunk_budget`` to compile the per-slot
decode step under a memory budget — the engine is the paper's serving
use-case (long-sequence inference on limited-memory hardware).

Plan caching: compilation is the expensive part of that integration, so the
engine warms a :class:`~repro.core.plan.PlanCache` at construction (pass
``plan_cache=`` a shared cache object or an on-disk directory, e.g. one
pre-built by ``python -m repro.tools.precompile``).  ``reconfigure()``
rebuilds the slot layout for a new (max_batch, max_len) and reuses any
previously compiled plan for that shape — a warm reconfiguration skips the
search/selection passes entirely.

Canonical-shape bucket executables (``canonical_bucket_exec``, default on):
the engine allocates its slot caches — and compiles its decode wave — at the
*bucket boundary* of ``max_len`` (``exec_len``), not at ``max_len`` itself.
Decode masking is position-driven, so the extra padded cache tail is
semantically inert.  One executable therefore serves every ``max_len``
inside a bucket: reconfiguring within a warm bucket performs zero traces and
zero XLA compiles (the jitted wave and its ``CompiledFunction`` are reused
object-identically; counter-asserted via ``bucket_exec_hits``).

Eviction: the engine writes serving telemetry (per-bucket hit counts,
last-use timestamps, compile cost) into the plan-cache entry metadata and —
when ``cache_max_entries`` is set — triggers
:meth:`~repro.core.plan.PlanCache.evict` with ``cache_policy`` at the only
background-safe points (construction / ``reconfigure``, when no requests are
in flight).
"""
from __future__ import annotations

import functools
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import stats
from ..models import model as M
from ..obs import accuracy as obs_accuracy
from ..obs import metrics as obs_metrics
from ..obs.tracing import span as _span


# ---------------------------------------------------------------------------
# Device-mesh plumbing (shared by both engines)
# ---------------------------------------------------------------------------

def _normalize_mesh(mesh):
    """``(MeshSpec, jax.Mesh)`` from a MeshSpec, a jax Mesh, or the CLI
    spelling ``"data=2,model=4"``.  ``(None, None)`` when no mesh."""
    if mesh is None:
        return None, None
    from ..core.meshspec import MeshSpec

    if isinstance(mesh, MeshSpec):
        return mesh, mesh.build_mesh()
    if isinstance(mesh, str):
        spec = MeshSpec.parse(mesh)
        return spec, spec.build_mesh()
    # a live jax Mesh: derive the serializable spec from its axes
    spec = MeshSpec(
        axes=tuple((str(n), int(mesh.shape[n])) for n in mesh.axis_names)
    )
    return spec, mesh


def _shard_params(cfg, params, mesh):
    """Place params onto ``mesh``: tensor-parallel via the launch-layer
    rules when the mesh has the ``model`` axis they name, replicated
    otherwise (computation follows data under GSPMD)."""
    from jax.sharding import NamedSharding, PartitionSpec

    names = set(mesh.axis_names)
    if "model" in names:
        from ..launch.sharding import param_pspecs, to_shardings

        shardings = to_shardings(mesh, param_pspecs(cfg, params, mesh))
        return jax.device_put(params, shardings)
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, rep), params)


def _dp_axis(mesh_spec) -> str:
    """The mesh axis a wave's slot/batch dim shards over."""
    names = mesh_spec.axis_names
    return "data" if "data" in names else names[0]


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # opt-out knob for the paged engine's prefix cache: when False this
    # request may still *match* cached prefixes but its own prompt is never
    # inserted (e.g. one-off prompts that would only pollute the radix tree)
    cache_prefix: bool = True
    generated: List[int] = field(default_factory=list)
    # logits that chose generated[0] (a device array, read by correctness
    # checks against a reference forward)
    first_logits: Any = None
    done: bool = False
    # monotonic timestamps (time.perf_counter): ttft_s/latency_s are
    # durations, immune to wall-clock steps.  Not comparable across
    # processes — serving spans/histograms are per-process anyway.
    submitted_at: float = field(default_factory=time.perf_counter)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at


class _EngineObs:
    """Step-boundary serving instruments shared by both engines.

    All recording happens at step boundaries with values the scheduler
    already holds on the host (no extra device syncs, nothing per token).
    ``enabled=False`` turns every record and span into a no-op — the
    observability-overhead benchmark (BENCH_obs.json) gates the on/off
    decode-throughput delta at <= 2%.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        reg = obs_metrics.default_registry()
        self.ttft = reg.histogram(
            "serve_ttft_seconds", obs_metrics.LATENCY_BUCKETS_S,
            "submit -> first token, per finished prefill")
        self.queue_wait = reg.histogram(
            "serve_queue_wait_seconds", obs_metrics.LATENCY_BUCKETS_S,
            "submit -> admission, per admitted request")
        self.step_latency = reg.histogram(
            "serve_step_latency_seconds", obs_metrics.LATENCY_BUCKETS_S,
            "one engine step (admit + ragged batch + sample + retire)")
        self.decode_tps = reg.histogram(
            "serve_decode_tok_per_s", obs_metrics.THROUGHPUT_BUCKETS,
            "decode tokens per second, per step carrying decode rows")
        self.pages_in_use = reg.gauge(
            "serve_pages_in_use", "KV-pool pages currently allocated")
        self.cache_hit_ratio = reg.gauge(
            "serve_cache_hit_ratio",
            "plan-cache (slot engine) or prefix-cache (paged) hit ratio")

    def span(self, name: str, **args):
        return _span(name, **args) if self.enabled else nullcontext()

    def record_admit(self, req: Request, now: float) -> None:
        if self.enabled:
            self.queue_wait.observe(max(now - req.submitted_at, 0.0))

    def record_first_token(self, req: Request) -> None:
        if self.enabled and req.ttft_s is not None:
            self.ttft.observe(req.ttft_s)

    def record_step(self, dt_s: float, decode_tokens: int) -> None:
        if not self.enabled:
            return
        self.step_latency.observe(dt_s)
        if decode_tokens > 0 and dt_s > 0:
            self.decode_tps.observe(decode_tokens / dt_s)


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int = 4,
        max_len: int = 256,
        autochunk_budget: Optional[float] = None,
        autotune: bool = False,
        plan_cache=None,
        bucket_lens: Optional[Any] = None,
        canonical_bucket_exec: bool = True,
        cache_policy: str = "lru",
        cache_max_entries: Optional[int] = None,
        greedy: bool = True,
        seed: int = 0,
        obs: bool = True,
        mesh=None,
    ):
        from ..core import ShapeBucketer
        from ..core.plan import PlanCache, as_plan_cache

        self.cfg = cfg
        # mesh: a MeshSpec, a jax Mesh, or "data=2,model=4".  Params are
        # placed onto the mesh (TP when its axes match the launch rules),
        # the decode wave jits under DP in_shardings over the slot dim,
        # and the compile pipeline plans by per-device sharded bytes.
        self.mesh_spec, self.mesh = _normalize_mesh(mesh)
        self.params = (
            _shard_params(cfg, params, self.mesh)
            if self.mesh is not None else params
        )
        self._obs = _EngineObs(obs)
        # allocator baseline for the device-side accuracy measurement
        # (None on backends without memory_stats, e.g. CPU)
        self._dev_base = obs_accuracy.device_bytes_in_use()
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.autochunk_budget = autochunk_budget
        # force the kernel autotune pass on cold compiles; the winning
        # KernelTuning persists in the plan (v4), so warm replays and bucket
        # hits reuse it with autotune_passes staying 0
        self.autotune = autotune
        # accept a PlanCache, a directory path, or None; with a budget set,
        # an in-memory cache is always created so that reconfigure() back to
        # a previously seen shape replays the stored plan instead of
        # re-searching
        self.plan_cache = as_plan_cache(plan_cache)
        if self.plan_cache is None and autochunk_budget is not None:
            self.plan_cache = PlanCache()
        if cache_policy not in PlanCache.POLICIES:
            raise ValueError(
                f"cache_policy must be one of {PlanCache.POLICIES},"
                f" got {cache_policy!r}"
            )
        self.cache_policy = cache_policy
        self.cache_max_entries = cache_max_entries
        # bucketed plan reuse: reconfigure() to a max_len in an already-seen
        # bucket replays that bucket's plan (zero search passes) instead of
        # searching the new length from scratch
        self.bucketer = ShapeBucketer(
            buckets=tuple(bucket_lens) if bucket_lens else None
        )
        # canonical-shape bucket executables: slots and the decode wave are
        # built at the bucket boundary of max_len, so the whole bucket is
        # served by ONE executable (max_len stays the logical request cap)
        self.canonical_bucket_exec = canonical_bucket_exec
        self.autochunk_result = None
        self._chunked_fn = None
        # (max_batch, exec_len) -> (decode_wave, prefill, autochunk_result):
        # a reconfigure inside a warm bucket restores these object-identically
        self._wave_cache: Dict[tuple, tuple] = {}
        self.exec_stats = {
            "wave_compiles": 0,
            "wave_reuses": 0,
            "evicted": 0,
        }

        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        self.n_decode_steps = 0
        self._init_slots()
        self._compile()

    @property
    def exec_len(self) -> int:
        """Cache/executable length: the bucket boundary of ``max_len``."""
        if not self.canonical_bucket_exec:
            return self.max_len
        return max(self.max_len, self.bucketer.canonical_dim(self.max_len))

    # ------------------------------------------------------------------
    def _init_slots(self):
        # each slot keeps its own B=1 cache; slots are stacked on a fresh
        # leading axis that the decode wave vmaps over.  Length is exec_len
        # (the bucket boundary): decode masking is position-driven, so the
        # padded tail beyond max_len is never attended to.
        cache1 = M.init_cache(self.cfg, 1, self.exec_len)
        self.cache = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (self.max_batch,) + x.shape
            ).copy(),
            cache1,
        )
        self.slot_req: List[Optional[Request]] = [None] * self.max_batch
        self.slot_pos = [0] * self.max_batch

    def _compile(self):
        from ..core import stats

        cfg, max_batch = self.cfg, self.max_batch
        # evictions can fire inside ChunkedFunction.compile (the config
        # knobs) or from our own idle-point trigger — attribute both
        ev0 = self.plan_cache.evictions if self.plan_cache is not None else 0
        wave_key = (max_batch, self.exec_len)
        cached = self._wave_cache.get(wave_key)
        if cached is not None:
            # warm bucket: restore the jitted wave + CompiledFunction
            # object-identically — zero traces, zero searches, zero XLA
            # compiles (the proof the serving smoke greps for)
            self._decode_wave, self._prefill, self.autochunk_result = cached
            self.exec_stats["wave_reuses"] += 1
            if self.canonical_bucket_exec:
                # only a canonical engine's reuse is a *bucket* hit; with
                # exact-length compilation this is plain same-shape reuse
                stats.bump("bucket_exec_hits")
            self._record_telemetry(hit=True)
            self._maybe_evict(ev0)
            return

        if self.canonical_bucket_exec:
            # cold bucket: this compile is the bucket's one boundary build
            # (counted for autochunk'd and plain waves alike, so the
            # hit/miss/compile ratios stay meaningful per engine class)
            stats.bump("bucket_exec_misses")
            stats.bump("bucket_exec_compiles")

        def _row_decode(cache_row, tok, pos):
            logits, nc = M.decode_step(
                cfg, self.params, cache_row, tok[None, None], pos
            )
            return logits[0, 0], nc

        decode_wave = jax.vmap(_row_decode)
        wave_mesh_spec = None
        if self.mesh_spec is not None:
            # DP over the slot dim of every wave input (cache leaves, toks,
            # pos).  Entries are axis *names*, not shapes — estimation
            # checks divisibility per concrete shape — so one spec covers
            # every reconfigure of this engine.
            from ..core.meshspec import MeshSpec

            dp = _dp_axis(self.mesh_spec)
            n_leaves = len(jax.tree_util.tree_leaves(self.cache)) + 2
            wave_mesh_spec = MeshSpec(
                axes=self.mesh_spec.axes,
                in_specs=tuple((dp,) for _ in range(n_leaves)),
                seq_axis=self.mesh_spec.seq_axis,
            )
        if self.autochunk_budget is not None:
            from ..core import ChunkConfig, ChunkedFunction

            if self._chunked_fn is None:
                # one transform for the engine's lifetime: reconfigure()
                # recompiles through it, reusing exact or bucketed plans
                self._chunked_fn = ChunkedFunction(
                    decode_wave,
                    ChunkConfig.from_scalar(
                        self.autochunk_budget,
                        weight_argnums=(),
                        autotune="on" if self.autotune else "auto",
                        canonical_bucket_exec=self.canonical_bucket_exec,
                        cache_policy=self.cache_policy,
                        cache_max_entries=self.cache_max_entries,
                        mesh_spec=wave_mesh_spec,
                    ),
                    cache=self.plan_cache,
                    bucketer=self.bucketer,
                )
            tok_spec = jax.ShapeDtypeStruct((max_batch,), jnp.int32)
            pos_spec = jax.ShapeDtypeStruct((max_batch,), jnp.int32)
            cache_spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.cache
            )
            # staged AOT: trace -> search (plan, cache/bucket-aware) -> compile
            # — the specs are already canonical (exec_len-shaped slots), so
            # this IS the bucket-boundary compile
            with self._obs.span("serve.compile", exec_len=self.exec_len):
                compiled = self._chunked_fn.compile(
                    cache_spec, tok_spec, pos_spec
                )
            self.autochunk_result = compiled.result
            res = compiled.result
            if (res.accuracy is not None and res.cache_key
                    and self.plan_cache is not None):
                self.plan_cache.record_accuracy(res.cache_key, res.accuracy)
            decode_wave = compiled.fn
        if self.mesh is not None:
            # DP-shard the wave over the mesh: every input's slot dim lands
            # on the data axis, params stay at their device_put shardings
            from jax.sharding import NamedSharding, PartitionSpec

            dp_sh = NamedSharding(
                self.mesh, PartitionSpec(_dp_axis(self.mesh_spec))
            )
            self._cache_sharding = jax.tree.map(lambda _: dp_sh, self.cache)
            self._decode_wave = jax.jit(
                decode_wave,
                in_shardings=(self._cache_sharding, dp_sh, dp_sh),
            )
        else:
            self._decode_wave = jax.jit(decode_wave)
        cfg, exec_len = self.cfg, self.exec_len
        self._prefill = jax.jit(
            lambda params, batch: M.prefill(cfg, params, batch, exec_len)
        )
        self.exec_stats["wave_compiles"] += 1
        self._wave_cache[wave_key] = (
            self._decode_wave, self._prefill, self.autochunk_result
        )
        self._record_telemetry(hit=False)
        self._maybe_evict(ev0)

    # ------------------------------------------------------------------
    def _record_telemetry(self, *, hit: bool) -> None:
        """Write serving telemetry into the plan-cache entry metadata."""
        res = self.autochunk_result
        if self.plan_cache is None or res is None or not res.cache_key:
            return
        self.plan_cache.record_use(
            res.cache_key,
            hit=hit,
            compile_s=res.elapsed_s,
            bucket=self.exec_len,
        )

    def _maybe_evict(self, evictions_before: int = 0) -> int:
        """Telemetry-driven cache eviction (background-safe trigger).

        Only called from construction / ``reconfigure`` — the engine is
        idle there, and eviction touches only the plan store, never a live
        executable.  ``evictions_before`` is the cache's eviction counter
        at compile start, so evictions the ChunkedFunction's own config
        knobs performed mid-compile are attributed to this engine too.
        """
        if self.plan_cache is None:
            return 0
        if self.cache_max_entries is not None:
            self.plan_cache.evict(
                policy=self.cache_policy, max_entries=self.cache_max_entries
            )
        n = self.plan_cache.evictions - evictions_before
        self.exec_stats["evicted"] += n
        return n

    def reconfigure(
        self,
        *,
        max_batch: Optional[int] = None,
        max_len: Optional[int] = None,
    ) -> None:
        """Re-shape the slot layout (and recompile the decode wave).

        Only legal while no requests are in flight.  A reconfiguration to a
        ``max_len`` inside an already-warm bucket reuses that bucket's
        canonical executable outright (zero traces, zero XLA compiles);
        otherwise, with a warm plan cache, the recompile replays the stored
        chunk plan for the new shape if one exists (e.g. pre-built by
        ``repro.tools.precompile``) instead of re-searching.
        """
        if any(r is not None for r in self.slot_req) or self.waiting:
            raise RuntimeError("reconfigure() requires an idle engine")
        if max_batch is not None:
            self.max_batch = max_batch
        if max_len is not None:
            self.max_len = max_len
        self._init_slots()
        self._compile()

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.waiting.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self):
        for slot in self._free_slots():
            if not self.waiting:
                break
            req = self.waiting.pop(0)
            self._obs.record_admit(req, time.perf_counter())
            toks = jnp.asarray([req.prompt], dtype=jnp.int32)
            with self._obs.span("serve.prefill_chunk", rid=req.rid,
                                tokens=len(req.prompt)):
                logits, cache1 = self._prefill(self.params, {"tokens": toks})
            self.cache = jax.tree.map(
                lambda full, r: full.at[slot].set(r), self.cache, cache1
            )
            if self.mesh is not None:
                # the row comes back laid out like the (sharded) weights;
                # the wave takes the cache slot-sharded
                self.cache = jax.device_put(self.cache, self._cache_sharding)
            # first token follows the engine's sampling mode, same as step():
            # greedy argmax, otherwise a categorical draw from the prefill
            # logits with the engine PRNG key
            req.first_logits = logits[0, -1]
            if self.greedy:
                first = int(jnp.argmax(logits[0, -1]))
            else:
                self.key, sub = jax.random.split(self.key)
                first = int(jax.random.categorical(sub, logits[0, -1]))
            req.generated.append(first)
            req.first_token_at = time.perf_counter()
            self._obs.record_first_token(req)
            self.slot_req[slot] = req
            self.slot_pos[slot] = len(req.prompt)

    def _retire(self):
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            hit_eos = req.eos_id is not None and req.generated and req.generated[-1] == req.eos_id
            if len(req.generated) >= req.max_new_tokens or hit_eos:
                req.done = True
                req.finished_at = time.perf_counter()
                self.finished.append(req)
                self.slot_req[i] = None

    # ------------------------------------------------------------------
    def step(self):
        """Admit -> decode one wave -> retire."""
        if not self._obs.enabled:
            return self._step_inner()
        t0 = time.perf_counter()
        with self._obs.span("serve.step"):
            rows = self._step_inner()
        if rows:
            dt = time.perf_counter() - t0
            self._obs.record_step(dt, rows)
            if self.plan_cache is not None:
                seen = self.plan_cache.hits + self.plan_cache.misses
                if seen:
                    self._obs.cache_hit_ratio.set(
                        self.plan_cache.hits / seen
                    )

    def _step_inner(self) -> int:
        with self._obs.span("serve.admit"):
            self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        toks = jnp.asarray(
            [
                (self.slot_req[i].generated[-1] if self.slot_req[i] else 0)
                for i in range(self.max_batch)
            ],
            dtype=jnp.int32,
        )
        pos = jnp.asarray(self.slot_pos, dtype=jnp.int32)
        with self._obs.span("serve.decode_wave", rows=len(active)):
            logits, self.cache = self._decode_wave(self.cache, toks, pos)
        self.n_decode_steps += 1
        if self.greedy:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            self.key, sub = jax.random.split(self.key)
            nxt = jax.random.categorical(sub, logits)
        nxt = jax.device_get(nxt)
        for i in active:
            self.slot_req[i].generated.append(int(nxt[i]))
            self.slot_pos[i] += 1
        self._retire()
        return len(active)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if not self.waiting and all(r is None for r in self.slot_req):
                break
            self.step()
        return self.finished

    def metrics(self) -> dict:
        """Aggregate serving metrics over finished requests."""
        done = self.finished
        toks = sum(len(r.generated) for r in done)
        ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
        lats = [r.latency_s for r in done if r.latency_s is not None]
        span = max((r.finished_at for r in done), default=0.0) - min(
            (r.submitted_at for r in done), default=0.0
        )
        out = {
            "requests": len(done),
            "tokens": toks,
            "decode_waves": self.n_decode_steps,
            "throughput_tok_s": toks / span if span > 0 else 0.0,
            "mean_ttft_s": sum(ttfts) / len(ttfts) if ttfts else 0.0,
            "mean_latency_s": sum(lats) / len(lats) if lats else 0.0,
        }
        out["exec_len"] = self.exec_len
        out["bucket_exec"] = dict(self.exec_stats)
        if self.mesh_spec is not None:
            out["mesh"] = {
                "axes": self.mesh_spec.describe(),
                "n_devices": self.mesh_spec.n_devices,
                "sharded_plans": stats.snapshot().get("sharded_plans", 0),
            }
        if self.plan_cache is not None:
            out["plan_cache"] = self.plan_cache.stats()
            if self.autochunk_result is not None and self.autochunk_result.cache_key:
                out["plan_telemetry"] = self.plan_cache.entry_meta(
                    self.autochunk_result.cache_key
                )
        acc = self.plan_accuracy()
        if acc is not None:
            out["plan_accuracy"] = acc.to_dict()
        return out

    def plan_accuracy(self) -> Optional[obs_accuracy.PlanAccuracy]:
        """Predicted-vs-measured activation peak of the serving plan.

        The interpret-mode record comes from compile time (search-time
        analytic prediction vs the emitted jaxpr's live-set watermark);
        on backends with allocator stats the measurement is upgraded to
        the ``memory_stats()`` peak delta observed since construction.
        """
        res = self.autochunk_result
        if res is None or res.accuracy is None:
            return None
        acc = obs_accuracy.with_device_measurement(
            res.accuracy, self._dev_base
        )
        if acc is not res.accuracy and self.plan_cache is not None \
                and res.cache_key:
            self.plan_cache.record_accuracy(res.cache_key, acc)
        return acc


# ===========================================================================
# Continuous batching on a paged KV pool
# ===========================================================================

@dataclass
class _SeqState:
    """A running sequence: scheduler-side view of one admitted request."""

    req: Request
    seq_id: int
    prefilled: int = 0        # prompt tokens already written into the pool
    kv_len: int = 0           # total tokens written (prompt part + generated)

    @property
    def in_prefill(self) -> bool:
        return self.prefilled < len(self.req.prompt)


class PagedServeEngine:
    """Continuous batching over a paged KV pool (the serving rewrite).

    Where :class:`ServeEngine` holds ``max_batch`` fixed slots — every
    admitted sequence paying ``exec_len`` worth of dense KV — this engine
    shares one :class:`~repro.serving.kv_pool.KVPool` across all sequences
    and schedules **mixed steps**: each engine step assembles one ragged
    batch holding a single token for every decoding sequence *plus* a
    planner-sized chunk of prompt for sequences still prefilling, and runs
    it through the ragged paged flash-attention kernel in one call.  The
    consequences CI asserts:

    * admission is bounded by **free pages, not slots** — a request is
      admitted iff the pool can reserve ``prompt + max_new_tokens`` worth
      of pages (so an admitted sequence can never OOM mid-decode), and
      retired sequences' pages are immediately reusable;
    * prefill is **chunked by the AutoChunk estimator**
      (:func:`~repro.core.estimation.plan_prefill_chunk`): the chunk size
      is the largest power of two whose one-block activation peak fits the
      engine's activation budget, so the planner and the batcher co-own
      one memory budget instead of a fixed ``--prefill-chunk`` knob;
    * KV memory has **zero padding waste**: sequences occupy exactly
      ``ceil(len / page_size)`` pages, TTFT is decoupled from the decode
      batch shape, and the only slack is the sub-page tail the pool's
      fragmentation counters report exactly.

    Two step shapes are compiled per engine lifetime: ``(max_seqs,
    prefill_chunk)`` for steps containing prefill rows and ``(max_seqs,
    1)`` for pure-decode steps.  Query padding inside a step is transient
    activation memory; the persistent KV is never padded.

    ``prefix_cache=True`` inserts the radix
    :class:`~repro.serving.prefix_cache.PrefixCache` between the allocator
    and this scheduler: admission matches the prompt against cached
    prefixes, seeds the page table with ref-shared pages (skipping their
    prefill entirely — chunks start at the divergence point, with only the
    partial boundary page copy-on-written), and completed prefills are
    inserted for the next request to share.  With ``spill_pages > 0``,
    out-of-pages admission becomes retry-after-spill: ref-free cached
    pages are evicted LRU to the host arena and restored on re-match.

    Supports the standard GQA attention families (dense decoders, causal,
    full attention); SSM/hybrid and MLA caches keep the slot engine.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_seqs: int = 4,
        max_len: int = 256,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        autochunk_budget: Optional[float] = None,
        autotune: bool = False,
        prefill_chunk="auto",
        prefix_cache: bool = False,
        spill_pages: int = 0,
        greedy: bool = True,
        seed: int = 0,
        obs: bool = True,
        mesh=None,
    ):
        from ..core.estimation import plan_prefill_chunk
        from .kv_pool import KVPool
        from .prefix_cache import PrefixCache

        if cfg.family not in ("dense", "vlm") or cfg.mla or not cfg.causal:
            raise ValueError(
                "PagedServeEngine serves causal dense/GQA decoders;"
                f" got family={cfg.family!r} mla={cfg.mla} causal={cfg.causal}"
            )
        if cfg.sliding_window is not None and cfg.sliding_window < max_len:
            raise ValueError("paged serving keeps the full context; use the"
                             " slot engine for sliding-window archs")
        self.cfg = cfg
        # mesh placement mirrors ServeEngine: params go tensor-parallel
        # when the mesh has the launch-rule axes, replicated otherwise.
        # Prefill *planning* stays deliberately unsharded/conservative —
        # the pool's pages are engine state, not activations.
        self.mesh_spec, self.mesh = _normalize_mesh(mesh)
        self.params = (
            _shard_params(cfg, params, self.mesh)
            if self.mesh is not None else params
        )
        params = self.params
        self.max_seqs = max_seqs
        self.max_len = max_len
        self.page_size = page_size
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.autochunk_budget = autochunk_budget
        self._obs = _EngineObs(obs)
        self._dev_base = obs_accuracy.device_bytes_in_use()
        self._accuracy: Optional[obs_accuracy.PlanAccuracy] = None
        # autotune the paged kernel's pages-per-grid-step per step width;
        # the in-process tune cache dedups repeat widths across engines
        self.autotune = autotune
        self.kernel_tuning = None

        if num_pages is None:
            # default capacity: every row of the step batch can hold a
            # max_len sequence (the paged win is that they rarely do)
            num_pages = max_seqs * (-(-max_len // page_size))
        self.pool = KVPool.for_config(
            cfg, num_pages=num_pages, page_size=page_size
        )
        # tensor parallelism over KV heads: each device of the mesh's
        # ``model`` axis holds its heads' K/V rows of every page, and runs
        # the paged kernel on them (see _step_fn)
        self._head_axis = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            m = dict(self.mesh.shape).get("model", 1)
            if m > 1 and cfg.n_kv_heads % m == 0 and cfg.n_heads % m == 0:
                self._head_axis = "model"
            self.pool.pages = jax.device_put(
                self.pool.pages,
                NamedSharding(self.mesh, P(None, None, None, self._head_axis)),
            )
        self.max_pages_per_seq = self.pool.pages_for(max_len)
        # prefix-sharing radix cache: admission matches cached prompt
        # prefixes onto ref-shared pool pages and skips their prefill;
        # spill_pages > 0 adds the host spill tier (see serving.prefix_cache)
        if spill_pages and not prefix_cache:
            raise ValueError("spill_pages requires prefix_cache=True")
        self.prefix_cache = (
            PrefixCache(self.pool, spill_pages=spill_pages)
            if prefix_cache else None
        )

        # planner-driven chunked prefill: the AutoChunk estimator sizes the
        # chunk from the activation budget (ratio of the full-prefill peak)
        if prefill_chunk == "auto":
            self.prefill_plan = plan_prefill_chunk(
                cfg,
                budget=autochunk_budget if autochunk_budget else 0.5,
                max_len=max_len,
            )
            self.prefill_chunk = min(self.prefill_plan.chunk, max_len)
        else:
            self.prefill_plan = None
            self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")

        self.waiting: List[Request] = []
        self.running: List[_SeqState] = []
        self.finished: List[Request] = []
        self._next_seq_id = 0
        self._step_fns: Dict[int, Any] = {}
        self.sched_stats = {
            "steps": 0,
            "mixed_steps": 0,
            "prefill_steps": 0,
            "decode_steps": 0,
            "prefill_chunks": 0,
            "decode_tokens": 0,
            "admission_refusals": 0,
            "step_compiles": 0,
            "prefix_hits": 0,
            "prefix_tokens_reused": 0,
            "spill_retries": 0,
        }

    # ------------------------------------------------------------------
    def _step_fn(self, q_max: int):
        """One jitted ragged step at query width ``q_max`` (compiled once)."""
        if q_max in self._step_fns:
            return self._step_fns[q_max]

        cfg = self.cfg
        from ..kernels import ops
        from ..kernels.paged_attention import (
            interleave_kv,
            paged_attention_blocked,
        )
        from ..models import layers as L

        S = self.max_seqs
        ps = self.page_size
        mp = self.max_pages_per_seq
        n_flat = self.pool.pages.shape[1] * ps        # includes trash page
        trash_slot = self.pool.trash_page * ps

        pages_per_step = 1
        if self.autotune:
            from ..kernels import autotune as _autotune

            tuning = _autotune.tune_sites(
                [{
                    "kind": "paged",
                    "page_size": ps, "max_pages": mp, "q_max": q_max,
                    "h": cfg.n_heads, "kv": cfg.n_kv_heads, "hd": cfg.hd,
                    "n_seqs": S,
                }],
                interpret=ops.interpret_default(),
            )
            if tuning.paged:
                pages_per_step = int(tuning.paged["pages_per_step"])
            self.kernel_tuning = tuning

        paged_attn = functools.partial(
            paged_attention_blocked, pages_per_step=pages_per_step,
            interpret=ops.interpret_default(),
        )
        if self._head_axis is not None:
            # a Pallas kernel cannot be partitioned by GSPMD: run it per
            # device on that device's query heads and pool heads
            from jax.sharding import PartitionSpec as P

            heads = P(None, None, self._head_axis)
            paged_attn = jax.shard_map(
                paged_attn, mesh=self.mesh,
                in_specs=(heads, heads, P(), P(), P()), out_specs=heads,
                check_vma=False,
            )

        def step(params, pages, tokens, q_lens, kv_lens, page_table):
            # the weights are an argument, never closed over: a closed-over
            # array would be baked into the program as a constant.
            # tokens: (S, q_max) int32; q_lens/kv_lens: (S,) int32 with
            # kv_lens counting context INCLUDING this step's new tokens;
            # page_table: (S, mp) int32
            positions = (kv_lens - q_lens)[:, None] + jnp.arange(
                q_max, dtype=jnp.int32
            )[None, :]
            valid = jnp.arange(q_max)[None, :] < q_lens[:, None]

            logical = jnp.clip(positions // ps, 0, mp - 1)
            phys = jnp.take_along_axis(page_table, logical, axis=1)
            slots = phys * ps + positions % ps
            slots = jnp.where(valid, slots, trash_slot).reshape(-1)

            def layer(h, inp):
                p, layer_pages = inp
                hn = L.apply_norm(cfg, h, p["ln1"])
                q, k, v = L.attn_project_qkv(cfg, p["attn"], hn, positions)
                new_kv = interleave_kv(
                    k.reshape(S * q_max, cfg.n_kv_heads, cfg.hd),
                    v.reshape(S * q_max, cfg.n_kv_heads, cfg.hd),
                ).astype(layer_pages.dtype)
                flat = layer_pages.reshape(n_flat, 2 * cfg.n_kv_heads, cfg.hd)
                layer_pages = flat.at[slots].set(new_kv).reshape(
                    layer_pages.shape)
                o = paged_attn(q, layer_pages, page_table, q_lens, kv_lens)
                h = h + o.reshape(S, q_max, -1) @ p["attn"]["wo"]
                hn = L.apply_norm(cfg, h, p["ln2"])
                return h + L.mlp(cfg, p["mlp"], hn), layer_pages

            h = L.embed(cfg, params["embed"], tokens)  # (S, q_max, d)
            if cfg.scan_layers:
                # one traced layer: compile time does not grow with depth
                h, pages = jax.lax.scan(layer, h, (params["blocks"], pages))
            else:
                new_pages = []
                for i, p in enumerate(params["blocks"]):
                    h, layer_pages = layer(h, (p, pages[i]))
                    new_pages.append(layer_pages)
                pages = jnp.stack(new_pages)

            h = L.apply_norm(cfg, h, params["final_norm"])
            last = h[jnp.arange(S), jnp.clip(q_lens - 1, 0, q_max - 1)]
            logits = L.unembed(cfg, params["embed"], last)   # (S, V)
            return logits, pages

        with self._obs.span("serve.step_compile", q_max=q_max):
            fn = jax.jit(step)
        self._step_fns[q_max] = fn
        self.sched_stats["step_compiles"] += 1
        return fn

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = {total} exceeds"
                f" max_len={self.max_len}"
            )
        self.waiting.append(req)

    def _reserve(self, sid: int, need: int, match) -> None:
        """One pool reservation, seeded by the prefix match when present.

        On :class:`OutOfPagesError` with the prefix cache enabled, asks the
        cache to release the shortfall (spill-to-host under pressure, LRU
        drop otherwise; the matched pages themselves are protected) and
        retries once — admission is retry-after-spill, not refuse.
        """
        from .kv_pool import OutOfPagesError

        kwargs = {}
        if match is not None and match.matched_tokens > 0:
            kwargs = dict(
                shared_pages=match.full_pages,
                shared_tokens=match.matched_tokens,
                boundary_page=match.boundary_page,
            )
        try:
            self.pool.reserve(sid, need, **kwargs)
            return
        except OutOfPagesError as e:
            if self.prefix_cache is None:
                raise
            shortfall = e.need - e.free
            protect = match.pages if match is not None else frozenset()
            self.sched_stats["spill_retries"] += 1
            if self.prefix_cache.release_pages(shortfall, protect=protect) < shortfall:
                raise
        self.pool.reserve(sid, need, **kwargs)

    def _admit(self):
        """FIFO admission bounded by pool pages, not batch slots."""
        from .kv_pool import OutOfPagesError

        while self.waiting and len(self.running) < self.max_seqs:
            req = self.waiting[0]
            need = len(req.prompt) + req.max_new_tokens
            sid = self._next_seq_id
            match = (
                self.prefix_cache.lock_prefix(req.prompt)
                if self.prefix_cache is not None else None
            )
            try:
                self._reserve(sid, need, match)
            except OutOfPagesError:
                # head-of-line blocking: wait for pages_freed, keep FIFO
                # order (any pages lock_prefix restored stay cached — they
                # remain evictable, nothing leaks)
                self.sched_stats["admission_refusals"] += 1
                stats.bump("admission_refusals")
                break
            matched = match.matched_tokens if match is not None else 0
            if matched > 0:
                stats.bump("prefix_hits")
                stats.bump("prefix_tokens_reused", matched)
                self.sched_stats["prefix_hits"] += 1
                self.sched_stats["prefix_tokens_reused"] += matched
            self._next_seq_id += 1
            self.waiting.pop(0)
            self._obs.record_admit(req, time.perf_counter())
            # matched tokens are already in the pool: prefill resumes at
            # the divergence point (kv_len/prefilled start there)
            self.running.append(
                _SeqState(req=req, seq_id=sid, prefilled=matched,
                          kv_len=matched)
            )
        return

    def _retire(self):
        still = []
        for st in self.running:
            req = st.req
            hit_eos = (
                req.eos_id is not None
                and req.generated
                and req.generated[-1] == req.eos_id
            )
            if not st.in_prefill and (
                len(req.generated) >= req.max_new_tokens or hit_eos
            ):
                req.done = True
                req.finished_at = time.perf_counter()
                self.finished.append(req)
                self.pool.free(st.seq_id)
            else:
                still.append(st)
        self.running = still

    # ------------------------------------------------------------------
    def step(self):
        """Admit -> one mixed ragged step -> sample -> retire."""
        if not self._obs.enabled:
            return self._step_inner()
        t0 = time.perf_counter()
        decoded0 = self.sched_stats["decode_tokens"]
        stepped0 = self.sched_stats["steps"]
        with self._obs.span("serve.step"):
            self._step_inner()
        if self.sched_stats["steps"] > stepped0:
            dt = time.perf_counter() - t0
            self._obs.record_step(
                dt, self.sched_stats["decode_tokens"] - decoded0
            )
            self._obs.pages_in_use.set(self.pool.pages_in_use)
            if self.prefix_cache is not None and self._next_seq_id:
                self._obs.cache_hit_ratio.set(
                    self.sched_stats["prefix_hits"] / self._next_seq_id
                )

    def _step_inner(self):
        with self._obs.span("serve.admit"):
            self._admit()
        if not self.running:
            return

        # schedule: every decode row rides along; prefill rows consume a
        # shared per-step chunk budget (the planner's activation bound)
        chunk_budget = self.prefill_chunk
        sched: List[tuple] = []                  # (state, n_new, tokens)
        n_prefill_rows = n_decode_rows = 0
        for st in self.running[: self.max_seqs]:
            prompt = st.req.prompt
            if st.in_prefill:
                if chunk_budget <= 0:
                    continue                      # waits for the next step
                take = min(chunk_budget, len(prompt) - st.prefilled)
                toks = prompt[st.prefilled: st.prefilled + take]
                chunk_budget -= take
                n_prefill_rows += 1
                sched.append((st, take, toks))
            else:
                n_decode_rows += 1
                sched.append((st, 1, [st.req.generated[-1]]))
        if not sched:
            return

        q_max = self.prefill_chunk if n_prefill_rows else 1
        import numpy as np

        S = self.max_seqs
        tokens = np.zeros((S, q_max), np.int32)
        q_lens = np.zeros((S,), np.int32)
        kv_lens = np.zeros((S,), np.int32)
        seq_ids: List[Optional[int]] = [None] * S
        for row, (st, take, toks) in enumerate(sched):
            tokens[row, :take] = toks
            q_lens[row] = take
            kv_lens[row] = st.kv_len + take
            seq_ids[row] = st.seq_id
            self.pool.ensure(st.seq_id, st.kv_len + take)
        page_table = self.pool.table_array(seq_ids, self.max_pages_per_seq)

        fn = self._step_fn(q_max)
        batch_span = (
            "serve.prefill_chunk" if n_prefill_rows else "serve.decode_wave"
        )
        with self._obs.span(batch_span, prefill_rows=n_prefill_rows,
                            decode_rows=n_decode_rows, q_max=q_max):
            logits, self.pool.pages = fn(
                self.params,
                self.pool.pages,
                jnp.asarray(tokens),
                jnp.asarray(q_lens),
                jnp.asarray(kv_lens),
                page_table,
            )

        # sample one token for every row that finished its context work
        need_rows = []
        for row, (st, take, _toks) in enumerate(sched):
            if st.in_prefill:
                st.prefilled += take
                st.kv_len += take
                if not st.in_prefill:
                    if self.prefix_cache is not None and st.req.cache_prefix:
                        # the prompt's KV is now complete in the pool:
                        # cache it so the next admission can share it
                        n_prompt = len(st.req.prompt)
                        self.prefix_cache.insert(
                            st.req.prompt,
                            self.pool.table(st.seq_id)[
                                : self.pool.pages_for(n_prompt)
                            ],
                        )
                    need_rows.append((row, st, True))
                else:
                    stats.bump("prefill_chunks")
                    self.sched_stats["prefill_chunks"] += 1
            else:
                st.kv_len += take
                need_rows.append((row, st, False))
        if need_rows:
            if self.greedy:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                self.key, sub = jax.random.split(self.key)
                nxt = jax.random.categorical(sub, logits)
            nxt = jax.device_get(nxt)
            now = time.perf_counter()
            for row, st, finished_prefill in need_rows:
                st.req.generated.append(int(nxt[row]))
                if finished_prefill:
                    st.req.first_logits = logits[row]
                    stats.bump("prefill_chunks")
                    self.sched_stats["prefill_chunks"] += 1
                    st.req.first_token_at = now
                    self._obs.record_first_token(st.req)
                else:
                    self.sched_stats["decode_tokens"] += 1

        self.sched_stats["steps"] += 1
        if n_prefill_rows and n_decode_rows:
            stats.bump("mixed_steps")
            self.sched_stats["mixed_steps"] += 1
        elif n_prefill_rows:
            self.sched_stats["prefill_steps"] += 1
        else:
            self.sched_stats["decode_steps"] += 1
        self._retire()

    def run(self, max_steps: int = 100_000) -> List[Request]:
        for _ in range(max_steps):
            if not self.waiting and not self.running:
                break
            self.step()
        return self.finished

    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        done = self.finished
        toks = sum(len(r.generated) for r in done)
        ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
        lats = [r.latency_s for r in done if r.latency_s is not None]
        span = max((r.finished_at for r in done), default=0.0) - min(
            (r.submitted_at for r in done), default=0.0
        )
        out = {
            "requests": len(done),
            "tokens": toks,
            "throughput_tok_s": toks / span if span > 0 else 0.0,
            "mean_ttft_s": sum(ttfts) / len(ttfts) if ttfts else 0.0,
            "mean_latency_s": sum(lats) / len(lats) if lats else 0.0,
            "prefill_chunk": self.prefill_chunk,
            "scheduler": dict(self.sched_stats),
            "kv_pool": self.pool.stats(),
        }
        if self.mesh_spec is not None:
            out["mesh"] = {
                "axes": self.mesh_spec.describe(),
                "n_devices": self.mesh_spec.n_devices,
                "sharded_plans": stats.snapshot().get("sharded_plans", 0),
            }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self.prefill_plan is not None:
            out["prefill_plan"] = {
                "chunk": self.prefill_plan.chunk,
                "budget_bytes": self.prefill_plan.budget_bytes,
                "peak_bytes": self.prefill_plan.peak_bytes,
                "fits": self.prefill_plan.fits,
            }
        acc = self.plan_accuracy()
        if acc is not None:
            out["plan_accuracy"] = acc.to_dict()
        return out

    def plan_accuracy(self) -> Optional[obs_accuracy.PlanAccuracy]:
        """Predicted-vs-measured peak for the prefill-chunk plan.

        *Predicted* is the planner's estimate for the chosen chunk —
        computed at construction, on the flattened one-block graph against
        a ``max_len`` context.  *Measured* (interpret fallback) is the
        live-set watermark of the same block step re-traced at the shapes
        the engine actually executes: KV rounded up to whole pool pages.
        The drift it surfaces is page-rounding plus the walkers'
        structural differences (flattened graph vs raw nested jaxpr); on
        backends with allocator stats the measurement upgrades to the
        ``memory_stats()`` peak delta since construction.
        """
        if self.prefill_plan is None:
            return None
        if self._accuracy is None:
            from ..core.estimation import _prefill_step_graph

            kv_exec = self.max_pages_per_seq * self.page_size
            g = _prefill_step_graph(self.cfg, self.prefill_chunk, kv_exec)
            measured = obs_accuracy.watermark_jaxpr(g.closed_jaxpr)
            self._accuracy = obs_accuracy.compare(
                self.prefill_plan.peak_bytes, measured, "interpret",
                chunk=self.prefill_chunk, kv_exec_len=kv_exec,
                budget_bytes=self.prefill_plan.budget_bytes,
            )
            obs_accuracy.publish(self._accuracy)
        return obs_accuracy.with_device_measurement(
            self._accuracy, self._dev_base
        )
