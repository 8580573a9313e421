"""Continuous-batching serving engine on the shared sharded-step API.

The engine owns a fixed-slot decode step and a fixed-capacity prefill
step — both built from ``dist/steps`` builders on one mesh, so jit
compiles each exactly once.  Requests stream through
``submit(prompt) -> Request``; each :meth:`Engine.step` tick either
prefills newly admitted requests (their prompt KV scattered into pages)
or decodes every in-flight slot, and finished requests are evicted so
their pages are immediately reusable.  Token selection is temperature
sampling (Gumbel-max), exact argmax at ``temperature == 0``.

    eng = Engine(registry.get("yi-6b-smoke"), EngineConfig(n_slots=4))
    req = eng.submit([1, 2, 3], max_new_tokens=8)
    for tok in eng.stream(req):
        ...
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import BASELINE
from repro.configs.base import ModelConfig, ShardingStrategy, WorkloadShape
from repro.dist import sharding as shd
from repro.dist import steps as dsteps
from repro.models.model import Model
from repro.obs.metrics import MetricsRegistry, compile_count, watch_compiles
from repro.obs.trace import Clock, Tracer, WallClock, phase
from repro.serve import paging
from repro.serve.scheduler import Request, Scheduler, StreamError


def _counter(metric: str, **labels):
    """A registry-backed counter exposed as a plain int attribute: the
    compatibility shim for the legacy ``eng.n_prefills``-style counters
    (reads hit the registry; writes — the elastic park/restore snapshot
    tuple-assigns them — become absolute registry puts)."""

    def _get(self) -> int:
        return int(self.metrics.value(metric, **labels))

    def _set(self, value) -> None:
        self.metrics.put(metric, value, **labels)

    return property(_get, _set)


def sample_tokens(logits, temps, key):
    """Per-row temperature sampling: Gumbel-max at ``temps > 0``, exact
    argmax at ``temps == 0`` (greedy decoding stays bit-deterministic)."""
    greedy = jnp.argmax(logits, axis=-1)
    g = jax.random.gumbel(key, logits.shape, jnp.float32)
    t = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jnp.argmax(logits.astype(jnp.float32) / t + g, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def serving_weights(x, sharding):
    """One parameter leaf as the engine holds it: bf16 (floating
    leaves), on ``sharding``.  A host (numpy) leaf is cast on the host
    and a device leaf on its own device, before the transfer."""
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.bfloat16:
        x = x.astype(jnp.bfloat16)
    return jax.device_put(x, sharding)


@dataclass(frozen=True)
class EngineConfig:
    """Fixed shapes of one engine.

    The decode step always compiles exactly once (fixed slots).  For
    attention-only architectures the prefill step does too: prompts are
    right-padded to ``max_prompt_len`` and causal masking makes padding
    invisible.  Seq-mixer (mamba/xlstm) recurrences are NOT masked by
    padding — pad tokens would contaminate the decode-time state — so
    sub-quadratic architectures prefill at the exact prompt length with
    a per-length compile cache instead.
    """

    n_slots: int = 4              # concurrent requests per step
    page_size: int = 16           # tokens per KV page
    max_seq_len: int = 128        # per-slot capacity (prompt + generated)
    max_prompt_len: int = 64      # prefill step capacity
    n_pages: int = 0              # 0 -> every slot can reach max_seq_len
    pad_id: int = 0               # prompt padding token
    prefill_chunk: int = 0        # >0: chunked prefill inside decode ticks
    dp_shards: int = 1            # page-pool shards over the data tier
    prefill_cache_cap: int = 8    # LRU bound on per-length prefill compiles

    def layout(self) -> dsteps.PagedLayout:
        assert self.max_seq_len % self.page_size == 0
        assert self.max_prompt_len % self.page_size == 0
        assert self.max_prompt_len <= self.max_seq_len
        ns = max(self.dp_shards, 1)
        assert self.n_slots % ns == 0, \
            f"dp_shards={ns} must divide n_slots={self.n_slots}"
        pps = self.max_seq_len // self.page_size
        n_pages = self.n_pages or self.n_slots * pps + ns
        assert n_pages % ns == 0, \
            f"dp_shards={ns} must divide n_pages={n_pages}"
        return dsteps.PagedLayout(page_size=self.page_size,
                                  pages_per_slot=pps, n_pages=n_pages,
                                  n_shards=ns)


class Engine:
    """Driver loop: admission -> prefill -> continuous decode.

    Observability: every timing stamp flows through ``self.clock`` (an
    injectable ``obs.trace.Clock``; wall time by default, a tick/sim
    clock under the event-model benches), counters live in
    ``self.metrics`` (an ``obs.MetricsRegistry``; the legacy
    ``n_prefills``-style attributes are shims over it), and an optional
    ``self.tracer`` records each finished request's lifecycle spans.
    ``tracer=None`` (default) keeps the hot path untraced.  Each tick
    is also a tree of ``jax.profiler`` annotations (see :meth:`step`),
    and ``serve_compiles_total{program}`` counts the compilations that
    happened inside a launch.
    """

    # legacy counter attributes, backed by the metrics registry
    n_prefills = _counter("serve_prefills_total")
    n_prefill_tokens = _counter("serve_prefill_tokens_total")
    n_decode_steps = _counter("serve_ticks_total", kind="decode")
    n_mixed_steps = _counter("serve_ticks_total", kind="mixed")
    n_generated = _counter("serve_generated_tokens_total")
    _pc_hits = _counter("serve_prefill_compile_cache_total", event="hit")
    _pc_misses = _counter("serve_prefill_compile_cache_total", event="miss")
    _pc_evictions = _counter("serve_prefill_compile_cache_total",
                             event="eviction")

    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig = EngineConfig(),
                 *, strategy: ShardingStrategy = BASELINE, mesh=None,
                 params=None, seed: int = 0, clock: Optional[Clock] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        assert not cfg.encoder_layers, \
            "serving engine: decoder-only architectures"
        assert cfg.pos_type in ("rope", "none"), \
            "per-slot positions need rope (or no) position encoding"
        self.cfg = cfg
        self.ecfg = ecfg
        self.clock = clock if clock is not None else WallClock()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer
        self.mesh = mesh if mesh is not None else shd.make_mesh(
            (1, 1), ("data", "model"), devices=jax.devices()[:1])
        self.strategy = strategy
        layout = ecfg.layout()
        self.layout = layout
        self.alloc = paging.PageAllocator(ecfg.n_slots, layout)
        # chunked prefill needs causal masking: seq-mixer recurrences
        # cannot skip the chunk's padded rows, so those archs keep the
        # classic prefill-then-decode tick
        self._chunked = ecfg.prefill_chunk > 0 and not cfg.sub_quadratic
        self.scheduler = Scheduler(
            self.alloc, ecfg.max_prompt_len,
            prefill_chunk=ecfg.prefill_chunk if self._chunked else 0,
            clock=self.clock)

        dshape = WorkloadShape(f"serve{ecfg.n_slots}", "decode",
                               ecfg.max_seq_len, ecfg.n_slots)
        raw_decode, din, dout = dsteps.build_decode_step(
            cfg, strategy, self.mesh, dshape, paged=layout)
        pshard, pool_sh = din[0], din[1]
        self._pshard, self._pool_sh = pshard, pool_sh
        self._repl = shd.replicated(self.mesh)

        def decode_fn(params, pool, tokens, block_table, lengths, temps,
                      key):
            logits, pool = raw_decode(params, pool, tokens, block_table,
                                      lengths)
            return sample_tokens(logits, temps, key), pool

        self._decode = jax.jit(
            decode_fn,
            in_shardings=(pshard, pool_sh, din[2], din[3], din[4],
                          self._repl, self._repl),
            out_shardings=(self._repl, pool_sh), donate_argnums=(1,))

        if self._chunked:
            raw_mixed, min_sh, _ = dsteps.build_mixed_step(
                cfg, strategy, self.mesh, dshape, paged=layout,
                chunk=ecfg.prefill_chunk)
            r = self._repl

            def mixed_fn(params, pool, tokens, block_table, lengths,
                         c_tokens, c_pages, c_start, c_len, c_null,
                         c_slot, c_final, temps, key):
                logits, c_logits, pool = raw_mixed(
                    params, pool, tokens, block_table, lengths,
                    c_tokens, c_pages, c_start, c_len, c_null)
                # a final chunk samples from its last REAL prompt row
                last = c_logits[jnp.maximum(c_len[0] - 1, 0)]
                logits = jnp.where(c_final,
                                   logits.at[c_slot].set(last), logits)
                return sample_tokens(logits, temps, key), pool

            self._mixed = jax.jit(
                mixed_fn,
                in_shardings=tuple(min_sh) + (r, r, r, r),
                out_shardings=(r, pool_sh), donate_argnums=(1,))
        # seq-mixer state is a recurrence over every prefilled token, so
        # padding would leak into it: those archs prefill at exact length
        self._exact_prefill = cfg.sub_quadratic
        self._prefill_cache: OrderedDict = OrderedDict()

        # weights are held in bf16, the compute dtype of every step, and
        # made or placed straight onto this engine's devices: no float32
        # copy and no unsharded copy ever sits on a chip
        if params is None:
            params = jax.jit(functools.partial(Model(cfg).init,
                                               dtype=jnp.bfloat16),
                             out_shardings=pshard)(
                jax.random.PRNGKey(seed))
        self.params = jax.tree_util.tree_map(serving_weights, params,
                                             pshard)
        self.pool = jax.jit(
            functools.partial(paging.init_pool, cfg, ecfg.n_slots, layout),
            out_shardings=pool_sh)()
        self._next_token = np.zeros((ecfg.n_slots,), np.int32)
        self._key = jax.random.PRNGKey(seed + 1)
        # n_prefills counts prefill COMPUTE passes (one-shot prefills and
        # mixed ticks that consumed prompt tokens) — a prefix-cache hit
        # that skips prompt work therefore lowers it.  All counters live
        # in self.metrics; the attribute writes seed their series.
        self.n_prefills = 0
        self.n_prefill_tokens = 0
        self.n_decode_steps = 0
        self.n_mixed_steps = 0
        self.n_generated = 0
        self.prefix_cache = None      # set by a fleet Router (fleet.py)
        self._tick = 0                # working step() calls: span labels
        watch_compiles()

    # -- park / adopt (elastic + canary promotion machinery) ----------------
    def snapshot_state(self) -> dict:
        """Freeze the engine's entire decode state host-side: the paged
        KV pool, the allocator's block table / lengths / free lists,
        the scheduler queues, each slot's next token, the sampling key
        and the compute counters.  The snapshot is mesh-agnostic (host
        arrays + plain Python bookkeeping), so a shape-identical engine
        on ANY mesh — or with DIFFERENT params, the canary-promotion
        path — can :meth:`adopt_state` it and resume in-flight requests
        at the exact token they were parked at."""
        al, sch = self.alloc, self.scheduler
        return {
            "pool": jax.device_get(self.pool),
            "block_table": al.block_table.copy(),
            "lengths": al.lengths.copy(),
            "reserved": al._reserved.copy(),
            "free_pages": list(al.free_pages),
            "free_slots": list(al.free_slots),
            "waiting": list(sch.waiting),
            "prefilling": list(getattr(sch, "prefilling", ())),
            "running": dict(sch.running),
            "n_finished": sch.n_finished,
            "next_token": self._next_token.copy(),
            "key": jax.device_get(self._key),
            "counters": (self.n_prefills, self.n_decode_steps,
                         self.n_generated),
        }

    def adopt_state(self, snap: dict) -> None:
        """Adopt a :meth:`snapshot_state` snapshot: the pool reshards
        onto this engine's mesh via ``device_put`` and the host
        bookkeeping copies over.  Because parking freezes the tick
        stream rather than replaying it (the sampling key rides the
        snapshot), generated tokens stay token-for-token identical to
        an uninterrupted run at any temperature."""
        from collections import deque
        p = snap
        self.pool = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), p["pool"], self._pool_sh)
        al, sch = self.alloc, self.scheduler
        al.block_table[:] = p["block_table"]
        al.lengths[:] = p["lengths"]
        al._reserved[:] = p["reserved"]
        al.free_pages = list(p["free_pages"])
        al.free_slots = list(p["free_slots"])
        sch.waiting = deque(p["waiting"])
        sch.prefilling = deque(p.get("prefilling", ()))
        sch.running = dict(p["running"])
        sch.n_finished = p["n_finished"]
        self._next_token[:] = p["next_token"]
        self._key = jnp.asarray(p["key"])
        self.n_prefills, self.n_decode_steps, self.n_generated = \
            p["counters"]

    # -- request API --------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               tenant: str = "default",
               ttft_slo_s: Optional[float] = None) -> Request:
        return self.scheduler.submit(Request(
            prompt=list(prompt), max_new_tokens=max_new_tokens,
            temperature=temperature, eos_id=eos_id, tenant=tenant,
            ttft_slo_s=ttft_slo_s, t_created=self.clock.now()))

    def _owns(self, req: Request) -> bool:
        """Is ``req`` in this engine's scheduler (queued, mid-prefill,
        or running)?"""
        sch = self.scheduler
        return (any(r is req for r in sch.waiting)
                or any(r is req for r in sch.prefilling)
                or any(r is req for r in sch.running.values()))

    def stream(self, req: Request) -> Iterator[int]:
        """Yield ``req``'s tokens as they are generated, pumping the
        engine (other in-flight requests advance too).

        Raises :class:`StreamError` if the engine runs out of work while
        ``req`` is unfinished — i.e. the request was never submitted
        here (or belongs to a different replica).  Ending the iterator
        silently would be indistinguishable from a completed stream.
        """
        emitted = 0
        while True:
            while emitted < len(req.tokens):
                yield req.tokens[emitted]
                emitted += 1
            if req.finished:
                return
            if not self.step():
                code = ("starved_request" if self._owns(req)
                        else "foreign_request")
                raise StreamError([{
                    "field": "request", "code": code,
                    "message": (
                        f"engine out of work with request rid={req.rid} "
                        f"unfinished (state={req.state}, "
                        f"{len(req.tokens)}/{req.max_new_tokens} tokens "
                        "emitted)"
                        + ("" if code == "starved_request" else
                           " — it was never submitted to this engine; "
                           "stream it from the replica that owns it")),
                }])

    def run(self) -> None:
        """Drive until every submitted request has finished."""
        while self.step():
            pass

    # -- engine ticks -------------------------------------------------------
    def step(self) -> bool:
        """One tick: admit + prefill new arrivals, else decode in-flight
        slots.  Returns False when there is no work.

        Chunked engines never stall decode behind a prompt: while any
        slot is mid-prefill the tick is *mixed* — one prompt chunk for
        the head admitting slot fused with a single-token decode of
        every fully prefilled slot.

        Each tick is one ``engine.step`` span (trace ``tick-<n>``, with
        ``tick`` and ``kind``) around its phases, ``obs.ENGINE_PHASES``;
        a one-shot tick repeats plan to metrics for each admitted
        request.
        """
        sch = self.scheduler
        if not sch.has_work:
            return False
        tr, n = self.tracer, self._tick
        trace = f"tick-{n}"
        with phase(tr, "engine.step", trace, tick=n) as tick:
            with phase(tr, "engine.admit", trace):
                admitted = sch.admit()
                if admitted and self.prefix_cache is not None \
                        and self._chunked:
                    # fleet prefix cache: copy cached pages for the
                    # longest page-aligned common prompt prefix into the
                    # slot's own pages (copy-on-adopt) and skip those
                    # prompt tokens
                    for req in admitted:
                        self.prefix_cache.adopt(self, req)
            nxt = sch.next_chunk() if self._chunked else None
            if nxt is not None:
                tick.set_metadata(kind="mixed")
                self._run_mixed(*nxt, trace)
            elif admitted and not self._chunked:
                tick.set_metadata(kind="prefill")
                for req in admitted:
                    self._run_prefill(req, trace)
            elif sch.running:
                tick.set_metadata(kind="decode")
                self._run_decode(trace)
            else:
                return False
        self._tick += 1
        return True

    def _split(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _launch(self, trace: str, program: str, fn, *args):
        """``engine.launch``: a fresh sampling key and the jitted call,
        which hands back the tick's tokens (still on the device) and the
        donated pool.  A compilation inside it counts toward
        ``serve_compiles_total{program}``."""
        with phase(self.tracer, "engine.launch", trace,
                   program=program) as launch:
            c0 = compile_count()
            tok, self.pool = fn(*args, self._split())
            compiled = compile_count() - c0
            if compiled:
                self.metrics.inc("serve_compiles_total", compiled,
                                 program=program)
                launch.set_metadata(compiled=compiled)
        return tok

    def _tick_obs(self, kind: str, n_tokens: int) -> None:
        """End-of-tick instrumentation: tick kind, tokens/tick, and the
        page-pool occupancy per shard AFTER this tick's emits/evictions
        settled.  Mixed/decode tick counts ride the legacy
        counter shims (``n_mixed_steps``/``n_decode_steps`` ARE
        ``serve_ticks_total{kind=...}``); one-shot prefill ticks have
        no legacy counter, so the tick count increments here."""
        m = self.metrics
        if kind == "prefill":
            m.inc("serve_ticks_total", kind="prefill")
        m.observe("serve_tokens_per_tick", n_tokens, kind=kind)
        for shard, used in enumerate(self.alloc.pages_in_use_by_shard()):
            m.set("serve_pages_in_use", used, shard=shard)

    def _prefill_for(self, prompt_len: int):
        """The jitted prefill for this prompt: one fixed-capacity compile
        for attention-only archs, a per-length cache for seq-mixer archs
        (exact length keeps padding out of the recurrent state)."""
        plen = prompt_len if self._exact_prefill \
            else self.ecfg.max_prompt_len
        fn = self._prefill_cache.get(plen)
        if fn is not None:
            self._pc_hits += 1
            self._prefill_cache.move_to_end(plen)
            return plen, fn
        self._pc_misses += 1
        cfg, ps = self.cfg, self.ecfg.page_size
        cap = paging.round_up(plen, ps)        # KV padded to a page boundary
        pshape = WorkloadShape(f"serve_prefill{plen}", "prefill", plen, 1)
        raw_prefill, _, bshard, _ = dsteps.build_prefill_step(
            cfg, self.strategy, self.mesh, pshape, ragged=True)

        def prefill_fn(params, tokens, last_index, pool, page_rows, slots,
                       temps, key):
            logits, pcache = raw_prefill(params, {"tokens": tokens},
                                         last_index)
            if cap != plen:
                pcache = paging.pad_prefill_cache(cfg, pcache, cap)
            pool = paging.scatter_prefill(cfg, pool, pcache, page_rows,
                                          slots)
            return sample_tokens(logits, temps, key), pool

        r = self._repl
        fn = jax.jit(
            prefill_fn,
            in_shardings=(self._pshard, bshard["tokens"], r,
                          self._pool_sh, r, r, r, r),
            out_shardings=(r, self._pool_sh), donate_argnums=(3,))
        self._prefill_cache[plen] = fn
        # LRU bound: a long-tail of exact prompt lengths (seq-mixer
        # archs) must not hold every compile alive forever
        while len(self._prefill_cache) > max(self.ecfg.prefill_cache_cap, 1):
            self._prefill_cache.popitem(last=False)
            self._pc_evictions += 1
        return plen, fn

    def _emit(self, req: Request, tok: int) -> None:
        req.tokens.append(tok)
        self.n_generated += 1
        if req.t_first is None:
            req.t_first = self.clock.now()
        if (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self.scheduler.finish(req)
            if req.ttft is not None:
                self.metrics.observe("serve_ttft_s", req.ttft)
                self.metrics.observe("serve_ttft_e2e_s", req.ttft_e2e)
            if self.tracer is not None:
                self.tracer.record_request(req)
        else:
            self._next_token[req.slot] = tok

    def _run_prefill(self, req: Request, trace: str = "") -> None:
        tr = self.tracer
        with phase(tr, "engine.plan", trace):
            ecfg, slot, plen = self.ecfg, req.slot, len(req.prompt)
            step_len, prefill = self._prefill_for(plen)
            tokens = np.full((1, step_len), ecfg.pad_id, np.int32)
            tokens[0, :plen] = req.prompt
            npg = -(-step_len // ecfg.page_size)
            page_rows = self.alloc.block_table[slot:slot + 1, :npg]
        tok = self._launch(
            trace, "prefill", prefill,
            self.params, tokens, np.array([plen - 1], np.int32), self.pool,
            np.ascontiguousarray(page_rows),
            np.array([slot], np.int32),
            np.array([req.temperature], np.float32))
        with phase(tr, "engine.sync", trace):
            tok = np.asarray(tok)
        with phase(tr, "engine.emit", trace):
            self.n_prefills += 1
            self.n_prefill_tokens += plen
            req.t_prefill_done = self.clock.now()
            self._emit(req, int(tok[0]))
        with phase(tr, "engine.metrics", trace):
            self._tick_obs("prefill", 1)

    def _run_mixed(self, req: Request, start: int, n: int,
                   trace: str = "") -> None:
        """One fused tick: decode every fully prefilled slot + consume
        ``n`` prompt tokens (positions ``start..start+n``) of ``req``."""
        tr = self.tracer
        with phase(tr, "engine.plan", trace):
            ecfg, slot = self.ecfg, req.slot
            final = start + n >= len(req.prompt)
            c_tokens = np.full((1, ecfg.prefill_chunk), ecfg.pad_id,
                               np.int32)
            c_tokens[0, :n] = req.prompt[start:start + n]
            c_pages = np.ascontiguousarray(
                self.alloc.block_table[slot:slot + 1])
            active = self.scheduler.decodable()     # slot -> request
            for s in active:
                self.alloc.ensure_page(s)
            bt = self.alloc.block_table.copy()
            lens = self.alloc.lengths.copy()
            # mid-prefill slots must not decode: the view parks them on
            # their null page at length 0 (the empty-slot convention)
            for r_ in self.scheduler.prefilling:
                bt[r_.slot, :] = self.alloc.null_page_of(r_.slot)
                lens[r_.slot] = 0
            temps = np.zeros((ecfg.n_slots,), np.float32)
            for s, r_ in active.items():
                temps[s] = r_.temperature
            if final:
                temps[slot] = req.temperature
        tok = self._launch(
            trace, "mixed", self._mixed,
            self.params, self.pool, self._next_token[:, None], bt, lens,
            c_tokens, c_pages, np.array([start], np.int32),
            np.array([n], np.int32),
            np.int32(self.alloc.null_page_of(slot)),
            np.int32(slot), np.bool_(final), temps)
        with phase(tr, "engine.sync", trace):
            tok = np.asarray(tok)
        with phase(tr, "engine.emit", trace):
            self.n_mixed_steps += 1
            if n > 0:
                self.n_prefills += 1          # this tick did prompt work
                self.n_prefill_tokens += n
            for s, r_ in active.items():
                self.alloc.advance(s)
                self._emit(r_, int(tok[s]))
            done = self.scheduler.chunk_done(req, n)
            if done:
                req.t_prefill_done = self.clock.now()
                self._emit(req, int(tok[slot]))
        with phase(tr, "engine.metrics", trace):
            self._tick_obs("mixed", len(active) + (1 if done else 0))

    def _run_decode(self, trace: str = "") -> None:
        tr = self.tracer
        with phase(tr, "engine.plan", trace):
            active = dict(self.scheduler.running)   # slot -> request
            for slot in active:
                self.alloc.ensure_page(slot)
            temps = np.zeros((self.ecfg.n_slots,), np.float32)
            for slot, req in active.items():
                temps[slot] = req.temperature
            bt = self.alloc.block_table.copy()
            lens = self.alloc.lengths.copy()
        tok = self._launch(trace, "decode", self._decode, self.params,
                           self.pool, self._next_token[:, None], bt, lens,
                           temps)
        with phase(tr, "engine.sync", trace):
            tok = np.asarray(tok)
        with phase(tr, "engine.emit", trace):
            self.n_decode_steps += 1
            for slot, req in active.items():
                self.alloc.advance(slot)
                self._emit(req, int(tok[slot]))
        with phase(tr, "engine.metrics", trace):
            self._tick_obs("decode", len(active))

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "n_prefills": self.n_prefills,
            "n_prefill_tokens": self.n_prefill_tokens,
            "n_decode_steps": self.n_decode_steps,
            "n_mixed_steps": self.n_mixed_steps,
            "n_generated": self.n_generated,
            "pages_in_use": self.alloc.pages_in_use(),
            "free_pages": len(self.alloc.free_pages),
            "mesh_shape": dict(self.mesh.shape),
            "dp_shards": self.layout.n_shards,
            "prefill_cache": {
                "size": len(self._prefill_cache),
                "cap": self.ecfg.prefill_cache_cap,
                "hits": self._pc_hits,
                "misses": self._pc_misses,
                "evictions": self._pc_evictions,
            },
        }
