"""Tests for the continuous-batching serving engine (repro.serve).

The paging acceptance bar: paged decode must match the contiguous-cache
path token-for-token under greedy sampling — on a (1, 1) mesh and on
the 8-device conftest mesh, through eviction/page-reuse, and when
requests are admitted mid-decode (continuous batching).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import BASELINE
from repro.configs.base import MambaConfig, ModelConfig
from repro.dist import sharding as shd
from repro.dist.steps import PagedLayout
from repro.models.model import Model
from repro.serve import Engine, EngineConfig, PageAllocator, sample_tokens
from repro.serve.scheduler import Request, Scheduler, SubmitError, WAITING

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128)
TINY_HYBRID = ModelConfig(name="tiny-hybrid", family="hybrid", n_layers=2,
                          d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                          vocab_size=128, block_pattern=("attn", "mamba"),
                          mamba=MambaConfig())

ECFG = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                    max_prompt_len=8)


def _weights(cfg):
    """The seed-0 weights cast to bf16, as an engine holds them, so the
    contiguous references below compute on exactly the engine's
    weights."""
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                  Model(cfg).init(jax.random.PRNGKey(0)))


def _mesh_2x4():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them)")
    return shd.make_mesh((2, 4), ("data", "model"))


def _greedy_decode(cfg, params, cache, first_tok, start, gen):
    out = [first_tok]
    step = jax.jit(Model(cfg).decode_step)
    tok = jnp.asarray([[first_tok]], jnp.int32)
    for i in range(gen - 1):
        logits, cache = step(params, cache, tok, jnp.int32(start + i))
        out.append(int(jnp.argmax(logits[0])))
        tok = jnp.asarray([[out[-1]]], jnp.int32)
    return out


def _contiguous_greedy(cfg, params, prompt, gen, cap=32):
    """Reference: the prompt alone through the contiguous-cache path
    (right-padded prefill — exact for attention-only archs)."""
    toks = np.zeros((1, cap), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, cache = Model(cfg).prefill(
        params, {"tokens": jnp.asarray(toks)},
        last_index=jnp.array([len(prompt) - 1]))
    return _greedy_decode(cfg, params, cache, int(jnp.argmax(logits[0])),
                          len(prompt), gen)


def _contiguous_greedy_exact(cfg, params, prompt, gen, cap=32):
    """Reference for seq-mixer archs: exact-length prefill (no padding
    can touch the recurrent state), KV padded afterwards for headroom."""
    from repro.serve.paging import pad_prefill_cache
    logits, cache = Model(cfg).prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    cache = pad_prefill_cache(cfg, cache, cap)
    return _greedy_decode(cfg, params, cache, int(jnp.argmax(logits[0])),
                          len(prompt), gen)


# ---------------------------------------------------------------------------
# Page allocator / scheduler units
# ---------------------------------------------------------------------------


def test_allocator_lifecycle_and_page_reuse():
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=9)
    alloc = PageAllocator(2, layout)
    s0 = alloc.admit(5, 3)                 # 2 prompt pages, 8 tokens total
    assert alloc.pages_in_use() == 2
    assert alloc.lengths[s0] == 5
    assert (alloc.block_table[s0, :2] != 0).all()
    # the write at position 8 crosses into a third page
    alloc.lengths[s0] = 8
    alloc.ensure_page(s0)
    assert alloc.pages_in_use() == 3
    used = [int(p) for p in alloc.block_table[s0] if p != 0]
    alloc.free(s0)
    assert alloc.pages_in_use() == 0
    assert alloc.lengths[s0] == 0
    # LIFO free list: the freed pages are handed out again first
    s1 = alloc.admit(12, 0)
    reused = [int(p) for p in alloc.block_table[s1] if p != 0]
    assert set(reused) == set(used)


def test_allocator_admission_is_length_aware():
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=5)
    alloc = PageAllocator(2, layout)       # 4 usable pages
    assert not alloc.can_admit(9, 8)       # 17 tokens > 16-token slot
    alloc.admit(5, 7)                      # reserves ceil(12/4) = 3 pages
    assert not alloc.can_admit(4, 1)       # only 1 unreserved page left
    assert alloc.can_admit(3, 1)           # exactly one page's worth


def test_submit_rejects_request_the_pool_can_never_hold():
    """A request that fits a slot but not the page pool must fail loudly
    at submit — a structured SubmitError, not wait forever."""
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=4)
    sched = Scheduler(PageAllocator(2, layout), max_prompt_len=8)
    with pytest.raises(SubmitError) as exc:
        sched.submit(Request(prompt=[1] * 8, max_new_tokens=8))  # 4 > 3 pages
    assert any(e["code"] == "exceeds_pool" for e in exc.value.errors)


def test_submit_error_collects_every_problem():
    """One SubmitError names every invalid field, SpecError-style."""
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=4)
    sched = Scheduler(PageAllocator(2, layout), max_prompt_len=8)
    with pytest.raises(SubmitError) as exc:
        sched.submit(Request(prompt=[], max_new_tokens=0, temperature=-1.0))
    codes = {(e["field"], e["code"]) for e in exc.value.errors}
    assert ("prompt", "bad_length") in codes
    assert ("max_new_tokens", "too_small") in codes
    assert ("temperature", "negative") in codes


def test_scheduler_first_fit_skips_oversized_head():
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=4)
    alloc = PageAllocator(3, layout)       # 3 usable pages
    sched = Scheduler(alloc, max_prompt_len=8)
    holder = sched.submit(Request(prompt=[1] * 2, max_new_tokens=2))
    assert sched.admit() == [holder]       # 1 page held -> 2 free
    big = sched.submit(Request(prompt=[1] * 8, max_new_tokens=4))   # 3 pages
    small = sched.submit(Request(prompt=[1] * 4, max_new_tokens=4))  # 2 pages
    admitted = sched.admit()
    assert admitted == [small] and big.state == WAITING
    sched.finish(holder)
    sched.finish(small)
    assert sched.admit() == [big]


# ---------------------------------------------------------------------------
# Paged == contiguous (greedy, token-for-token)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [TINY, TINY_HYBRID],
                         ids=["dense", "hybrid"])
def test_paged_matches_contiguous_single_device(cfg):
    # hybrids prefill at exact length (pad tokens must never reach the
    # mamba recurrence), so their reference prefills unpadded too
    ref = (_contiguous_greedy_exact if cfg.sub_quadratic
           else _contiguous_greedy)
    params = _weights(cfg)
    eng = Engine(cfg, ECFG, params=params)
    r1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    r2 = eng.submit([7, 8, 9], max_new_tokens=6)
    eng.run()
    assert r1.tokens == ref(cfg, params, [1, 2, 3, 4, 5], 6)
    assert r2.tokens == ref(cfg, params, [7, 8, 9], 6)


def test_paged_matches_contiguous_on_8dev_mesh():
    mesh = _mesh_2x4()
    params = _weights(TINY)
    eng = Engine(TINY, EngineConfig(n_slots=4, page_size=4, max_seq_len=32,
                                    max_prompt_len=8),
                 strategy=BASELINE, mesh=mesh, params=params)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14], [2, 4]]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    for req, prompt in zip(reqs, prompts):
        assert req.tokens == _contiguous_greedy(TINY, params, prompt, 5)


def test_continuous_batching_admits_mid_decode():
    """ISSUE acceptance: a request admitted while others are mid-decode
    completes with greedy output identical to running it alone through
    the contiguous-cache path."""
    params = _weights(TINY)
    eng = Engine(TINY, ECFG, params=params)
    early = eng.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    eng.step()                              # prefill
    eng.step()                              # decode: early is in flight
    assert not early.finished and len(early.tokens) >= 2
    late = eng.submit([7, 8, 9], max_new_tokens=6)
    eng.run()
    assert early.finished and late.finished
    assert early.tokens == _contiguous_greedy(TINY, params,
                                              [1, 2, 3, 4, 5], 8)
    assert late.tokens == _contiguous_greedy(TINY, params, [7, 8, 9], 6)


def test_eviction_frees_pages_and_reuse_stays_correct():
    """Page pressure: the second request cannot be admitted until the
    first finishes and is evicted; its decode then runs on the recycled
    pages and must still match the contiguous path."""
    params = _weights(TINY)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=16,
                        max_prompt_len=8, n_pages=5)   # 4 usable pages
    eng = Engine(TINY, ecfg, params=params)
    r1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)  # 3 pages worst-case
    r2 = eng.submit([7, 8, 9], max_new_tokens=4)        # needs 2 more
    eng.step()
    assert r1.state != WAITING and r2.state == WAITING
    pages_r1 = {int(p) for p in eng.alloc.block_table[r1.slot] if p != 0}
    assert pages_r1, "first request must hold pages"
    eng.run()
    assert r1.finished and r2.finished
    assert eng.alloc.pages_in_use() == 0               # all evicted
    assert r1.tokens == _contiguous_greedy(TINY, params,
                                           [1, 2, 3, 4, 5], 4)
    assert r2.tokens == _contiguous_greedy(TINY, params, [7, 8, 9], 4)


# ---------------------------------------------------------------------------
# Temperature sampling
# ---------------------------------------------------------------------------


def test_sample_tokens_zero_temperature_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    temps = jnp.zeros((4,))
    tok = sample_tokens(logits, temps, jax.random.PRNGKey(1))
    assert (np.asarray(tok) == np.asarray(jnp.argmax(logits, -1))).all()


def test_sample_tokens_mixed_temperatures():
    logits = jnp.zeros((2, 64)).at[0, 3].set(10.0).at[1, 3].set(10.0)
    temps = jnp.array([0.0, 8.0])
    toks = set()
    for s in range(12):
        tok = np.asarray(sample_tokens(logits, temps,
                                       jax.random.PRNGKey(s)))
        assert tok[0] == 3                  # greedy row pinned
        toks.add(int(tok[1]))
    assert len(toks) > 1, "hot row must actually sample"


def test_engine_temperature_threading_is_seeded():
    params = _weights(TINY)

    def run(seed):
        eng = Engine(TINY, ECFG, params=params, seed=seed)
        req = eng.submit([1, 2, 3], max_new_tokens=6, temperature=1.5)
        eng.run()
        return req.tokens

    assert run(0) == run(0), "same seed, same stream"
    greedy = _contiguous_greedy(TINY, params, [1, 2, 3], 6)
    assert any(run(s) != greedy for s in (0, 1, 2)), \
        "temperature sampling should diverge from greedy"


class _ContiguousSampler:
    """Reference decoder for temperature>0: the contiguous-cache path
    driven with the engine's exact PRNG key stream and slot layout.

    ``sample_tokens`` draws Gumbel noise for the full (n_slots, vocab)
    logits block from ONE key per tick, and each row's argmax depends
    only on (key, row, that row's logits) — so a per-request contiguous
    cache plus the right (key, slot row) reproduces the engine's stream
    token for token, including requests admitted mid-decode.
    """

    def __init__(self, cfg, params, n_slots, seed, cap=32):
        self.cfg, self.params = cfg, params
        self.n_slots, self.cap = n_slots, cap
        self.key = jax.random.PRNGKey(seed + 1)    # mirrors Engine._key
        self.model = Model(cfg)
        self.step_fn = jax.jit(self.model.decode_step)
        self.live = {}                             # slot -> dict

    def _split(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def prefill(self, req):
        """One engine prefill tick for ``req`` (consumes one key)."""
        k = self._split()
        toks = np.zeros((1, self.cap), np.int32)
        toks[0, :len(req.prompt)] = req.prompt
        logits, cache = self.model.prefill(
            self.params, {"tokens": jnp.asarray(toks)},
            last_index=jnp.array([len(req.prompt) - 1]))
        tok = int(np.asarray(sample_tokens(
            logits, jnp.array([req.temperature]), k))[0])
        self.live[req.slot] = {"cache": cache, "pos": len(req.prompt),
                               "tok": tok, "temp": req.temperature}
        return tok

    def decode(self, slots):
        """One engine decode tick for the active ``slots`` (one key)."""
        k = self._split()
        logits = jnp.zeros((self.n_slots, self.cfg.vocab_size))
        temps = np.zeros((self.n_slots,), np.float32)
        for s in slots:
            st = self.live[s]
            row, st["cache"] = self.step_fn(
                self.params, st["cache"],
                jnp.asarray([[st["tok"]]], jnp.int32), jnp.int32(st["pos"]))
            st["pos"] += 1
            logits = logits.at[s].set(row[0])
            temps[s] = st["temp"]
        toks = np.asarray(sample_tokens(logits, jnp.asarray(temps), k))
        out = {}
        for s in slots:
            self.live[s]["tok"] = out[s] = int(toks[s])
        return out

    def mixed(self, slots, req, final):
        """One engine *mixed* tick (one key): decode ``slots`` plus, on
        ``req``'s final chunk, its first token from a whole-prompt
        contiguous prefill — the chunked engine's fused step samples the
        admitting slot's row from the same tick's key."""
        k = self._split()
        logits = jnp.zeros((self.n_slots, self.cfg.vocab_size))
        temps = np.zeros((self.n_slots,), np.float32)
        for s in slots:
            st = self.live[s]
            row, st["cache"] = self.step_fn(
                self.params, st["cache"],
                jnp.asarray([[st["tok"]]], jnp.int32), jnp.int32(st["pos"]))
            st["pos"] += 1
            logits = logits.at[s].set(row[0])
            temps[s] = st["temp"]
        if final:
            toks = np.zeros((1, self.cap), np.int32)
            toks[0, :len(req.prompt)] = req.prompt
            pl, cache = self.model.prefill(
                self.params, {"tokens": jnp.asarray(toks)},
                last_index=jnp.array([len(req.prompt) - 1]))
            logits = logits.at[req.slot].set(pl[0])
            temps[req.slot] = req.temperature
            self.live[req.slot] = {"cache": cache,
                                   "pos": len(req.prompt),
                                   "tok": None, "temp": req.temperature}
        toks_ = np.asarray(sample_tokens(logits, jnp.asarray(temps), k))
        out = {}
        for s in list(slots) + ([req.slot] if final else []):
            self.live[s]["tok"] = out[s] = int(toks_[s])
        return out


def test_paged_matches_contiguous_at_temperature():
    """ISSUE satellite: the paged==contiguous invariant extended past
    greedy — identical PRNG key => token-for-token identical sampled
    streams, with a second request admitted mid-decode."""
    params = _weights(TINY)
    eng = Engine(TINY, ECFG, params=params, seed=3)
    ref = _ContiguousSampler(TINY, params, ECFG.n_slots, seed=3)

    r1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=8, temperature=0.9)
    expect = {}

    def tick():
        """Advance engine + reference one tick in lockstep."""
        admitted = eng.scheduler.admit()
        if admitted:
            for req in admitted:
                eng._run_prefill(req)
                expect[req.rid] = [ref.prefill(req)]
        else:
            active = sorted(s for s in eng.scheduler.running)
            reqs = dict(eng.scheduler.running)
            eng._run_decode()
            for slot, tok in ref.decode(active).items():
                expect[reqs[slot].rid].append(tok)

    tick()                                    # prefill r1
    tick(); tick()                            # r1 mid-decode
    assert not r1.finished and len(r1.tokens) == 3
    r2 = eng.submit([7, 8, 9], max_new_tokens=6, temperature=1.7)
    while eng.scheduler.has_work:
        tick()
    assert r1.finished and r2.finished
    assert r1.tokens == expect[r1.rid][:len(r1.tokens)]
    assert r2.tokens == expect[r2.rid][:len(r2.tokens)]
    # temperature actually bites: at least one stream left the greedy path
    g1 = _contiguous_greedy(TINY, params, [1, 2, 3, 4, 5], 8)
    g2 = _contiguous_greedy(TINY, params, [7, 8, 9], 6)
    assert r1.tokens != g1 or r2.tokens != g2


# ---------------------------------------------------------------------------
# Chunked prefill (mixed decode+prefill ticks)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [2, 3, 8], ids=["c2", "c3", "c8"])
def test_chunked_prefill_greedy_identical_to_legacy(chunk):
    """ISSUE acceptance (pinned invariant): with greedy sampling the
    chunked engine's outputs are token-for-token identical to the
    legacy prefill-then-decode engine — including a request admitted
    mid-decode whose prompt trickles in across several mixed ticks."""
    params = _weights(TINY)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                        max_prompt_len=8, prefill_chunk=chunk)
    eng = Engine(TINY, ecfg, params=params)
    early = eng.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    eng.step()                              # first chunk (or whole prompt)
    while eng.scheduler.prefilling:
        eng.step()
    eng.step()                              # early decodes
    assert not early.finished and early.tokens
    late = eng.submit([7, 8, 9, 10, 11, 12, 13], max_new_tokens=6)
    eng.run()
    assert early.tokens == _contiguous_greedy(TINY, params,
                                              [1, 2, 3, 4, 5], 8)
    assert late.tokens == _contiguous_greedy(
        TINY, params, [7, 8, 9, 10, 11, 12, 13], 6)
    assert eng.n_mixed_steps > 0


def test_chunked_prefill_page_reuse_stays_correct():
    """Evicted pages re-used by a chunked prefill still decode exactly:
    the second request's chunks land on the first's recycled pages."""
    params = _weights(TINY)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=16,
                        max_prompt_len=8, n_pages=5,     # 4 usable pages
                        prefill_chunk=2)
    eng = Engine(TINY, ecfg, params=params)
    r1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)  # 3 pages worst-case
    r2 = eng.submit([7, 8, 9], max_new_tokens=4)        # needs 2 more
    eng.step()
    assert r1.state != WAITING and r2.state == WAITING
    pages_r1 = {int(p) for p in eng.alloc.block_table[r1.slot] if p != 0}
    eng.run()
    assert r1.finished and r2.finished
    assert r1.tokens == _contiguous_greedy(TINY, params,
                                           [1, 2, 3, 4, 5], 4)
    assert r2.tokens == _contiguous_greedy(TINY, params, [7, 8, 9], 4)
    assert pages_r1, "first request must have held pages"


def test_chunked_admission_mid_decode_at_temperature():
    """ISSUE satellite: a request admitted mid-decode under chunked
    prefill at temperature>0 — lockstep against the contiguous sampler
    driven with the engine's exact key stream (mixed ticks consume one
    key each, like any other tick)."""
    params = _weights(TINY)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                        max_prompt_len=8, prefill_chunk=2)
    eng = Engine(TINY, ecfg, params=params, seed=3)
    ref = _ContiguousSampler(TINY, params, ecfg.n_slots, seed=3)
    expect = {}

    def tick():
        eng.scheduler.admit()
        nxt = eng.scheduler.next_chunk()
        if nxt is not None:
            req, start, n = nxt
            final = start + n >= len(req.prompt)
            active = sorted(eng.scheduler.decodable())
            reqs = dict(eng.scheduler.running)
            eng._run_mixed(req, start, n)
            for slot, tok in ref.mixed(active, req, final).items():
                expect.setdefault(reqs[slot].rid, []).append(tok)
        else:
            active = sorted(eng.scheduler.running)
            reqs = dict(eng.scheduler.running)
            eng._run_decode()
            for slot, tok in ref.decode(active).items():
                expect.setdefault(reqs[slot].rid, []).append(tok)

    r1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=8, temperature=0.9)
    tick(); tick(); tick()           # 3 chunk ticks: prefill done + decode
    tick()
    assert not r1.finished and r1.tokens
    r2 = eng.submit([7, 8, 9, 10, 11], max_new_tokens=6, temperature=1.7)
    while eng.scheduler.has_work:
        tick()
    assert r1.finished and r2.finished
    assert r1.tokens == expect[r1.rid][:len(r1.tokens)]
    assert r2.tokens == expect[r2.rid][:len(r2.tokens)]
    # temperature actually bites
    g1 = _contiguous_greedy(TINY, params, [1, 2, 3, 4, 5], 8)
    g2 = _contiguous_greedy(TINY, params, [7, 8, 9, 10, 11], 6)
    assert r1.tokens != g1 or r2.tokens != g2


def test_chunked_prefill_falls_back_for_seq_mixers():
    """Seq-mixer recurrences cannot skip chunk padding: a hybrid engine
    with prefill_chunk set must silently keep exact prefill-then-decode
    and still match its reference."""
    params = _weights(TINY_HYBRID)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                        max_prompt_len=8, prefill_chunk=2)
    eng = Engine(TINY_HYBRID, ecfg, params=params)
    assert not eng._chunked
    req = eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    eng.run()
    assert req.tokens == _contiguous_greedy_exact(TINY_HYBRID, params,
                                                  [1, 2, 3, 4, 5], 6)


# ---------------------------------------------------------------------------
# Data-parallel page-pool sharding
# ---------------------------------------------------------------------------


def test_sharded_allocator_keeps_pages_shard_local():
    """Per-shard free lists: a slot only ever owns its shard's pages,
    each shard has its own null page, and the elastic park/adopt
    free-list round-trip (property assignment) survives sharding."""
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=18,
                         n_shards=2)
    alloc = PageAllocator(4, layout)       # slots 0,1 -> shard 0; 2,3 -> 1
    assert alloc.null_page_of(0) == 0 and alloc.null_page_of(2) == 9
    assert (alloc.block_table[3] == 9).all()
    s0 = alloc.admit(5, 3)                 # shard 0
    s1 = alloc.admit(5, 3)
    s2 = alloc.admit(5, 3)                 # must land on shard 1
    assert {alloc.shard_of(s0), alloc.shard_of(s1)} == {0}
    assert alloc.shard_of(s2) == 1
    assert all(1 <= p <= 8 for p in alloc.block_table[s0, :2])
    assert all(10 <= p <= 17 for p in alloc.block_table[s2, :2])
    snap = list(alloc.free_pages)          # executor park path
    alloc.free_pages = snap                # executor adopt path
    assert list(alloc.free_pages) == snap
    alloc.free(s2)
    assert alloc.pages_in_use() == 4
    # LIFO within the shard: s2's pages come back first on shard 1
    s3 = alloc.admit(8, 0)
    assert alloc.shard_of(s3) == 1


def test_sharded_allocator_admission_is_shard_aware():
    """A request that no single shard can hold is not admitted even if
    the pool-wide free count would fit it."""
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=8,
                         n_shards=2)                  # 3 usable pages/shard
    alloc = PageAllocator(2, layout)
    alloc.admit(8, 0)          # 2 pages on shard 0 -> 1 left there
    assert not alloc.can_admit(12, 4)    # 4 pages: neither shard has them
    assert alloc.can_admit(8, 4)         # 3 pages: shard 1 still can


def test_paged_matches_contiguous_dp_sharded_pool():
    """ISSUE acceptance: paged==contiguous greedy parity holds with the
    page pool and block table sharded over the data axis of a (2, 4)
    mesh — legacy and chunked engines both."""
    mesh = _mesh_2x4()
    params = _weights(TINY)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14], [2, 4]]
    want = [_contiguous_greedy(TINY, params, p, 5) for p in prompts]
    for chunk in (0, 3):
        eng = Engine(TINY, EngineConfig(n_slots=4, page_size=4,
                                        max_seq_len=32, max_prompt_len=8,
                                        dp_shards=2, prefill_chunk=chunk),
                     strategy=BASELINE, mesh=mesh, params=params)
        assert eng.layout.n_shards == 2
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        assert [r.tokens for r in reqs] == want, f"chunk={chunk}"


# ---------------------------------------------------------------------------
# Prefill compile cache (LRU)
# ---------------------------------------------------------------------------


def test_prefill_compile_cache_is_lru_bounded():
    """Seq-mixer archs compile per exact prompt length; the LRU cap
    bounds that and the stats surface hits/misses/evictions."""
    params = _weights(TINY_HYBRID)
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                        max_prompt_len=8, prefill_cache_cap=2)
    eng = Engine(TINY_HYBRID, ecfg, params=params)
    for plen in (2, 3, 4, 2):       # 4 evicts 2 (LRU), then 2 recompiles
        req = eng.submit(list(range(1, plen + 1)), max_new_tokens=2)
        eng.run()
        assert req.finished
    pc = eng.stats()["prefill_cache"]
    assert pc["size"] <= 2 and pc["cap"] == 2
    assert pc["misses"] == 4 and pc["evictions"] >= 2 and pc["hits"] == 0
    # attention archs share ONE padded compile: all hits after the first
    eng2 = Engine(TINY, ecfg, params=_weights(TINY))
    for plen in (2, 3, 4):
        eng2.submit(list(range(1, plen + 1)), max_new_tokens=2)
    eng2.run()
    pc2 = eng2.stats()["prefill_cache"]
    assert pc2["misses"] == 1 and pc2["hits"] == 2


# ---------------------------------------------------------------------------
# Streaming API
# ---------------------------------------------------------------------------


def test_stream_yields_tokens_and_advances_other_requests():
    params = _weights(TINY)
    eng = Engine(TINY, ECFG, params=params)
    a = eng.submit([1, 2, 3, 4], max_new_tokens=5)
    b = eng.submit([5, 6], max_new_tokens=5)
    got = list(eng.stream(a))
    assert got == a.tokens and len(got) == 5
    assert b.finished, "pumping one stream drives the whole batch"


# ---------------------------------------------------------------------------
# Operator-driven serving (ISSUE acceptance)
# ---------------------------------------------------------------------------


def test_minicluster_allocation_hosts_serve_engine():
    """A MiniCluster-allocated ServeExecutor runs the engine on the
    submesh its ResourceSet describes; serve jobs flow through the Flux
    queue like train jobs."""
    from repro.core import (FluxMiniCluster, JobSpec, JobState,
                            MiniClusterSpec, NetModel, ResourceGraph,
                            ServeExecutor, SimClock)
    from repro.serve import EngineConfig as ECfg
    clock = SimClock(seed=0)
    net = NetModel()
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=2, chips_per_host=4)
    ex = ServeExecutor(clock, net, n_requests=2, prompt_len=6, max_new=3,
                       engine_config=ECfg(n_slots=2, page_size=4,
                                          max_seq_len=16,
                                          max_prompt_len=8))
    mc = FluxMiniCluster(clock, net, fleet,
                         MiniClusterSpec(name="srv", size=2), executor=ex)
    mc.create()
    mc.wait_ready()
    job = mc.instance.submit(JobSpec(n_nodes=2, walltime=1e9,
                                     command="lammps-proxy-smoke",
                                     args={"max_new": 3}))
    clock.run(until=clock.now + 600)
    assert job.state == JobState.INACTIVE
    assert job.result == "completed"
    rec = ex.ran[job.jobid]
    assert rec["n_tokens"] == rec["n_requests"] * 3
    assert rec["tokens_per_s"] > 0
    assert rec["hosts"] == list(job.allocation.hosts)
    if len(jax.devices()) >= 8:
        assert rec["mesh_shape"] == (2, 4)
        assert rec["n_devices"] == 8

# ---------------------------------------------------------------------------
# Stats accounting, TTFT stamping, stream truncation (fleet bugfix sweep)
# ---------------------------------------------------------------------------


def test_stats_page_conservation_dp_sharded():
    """Page conservation under dp_shards > 1: at every tick,
    free_pages + pages_in_use must equal the usable pool (n_pages minus
    one null page per shard)."""
    mesh = _mesh_2x4()
    params = _weights(TINY)
    ecfg = EngineConfig(n_slots=4, page_size=4, max_seq_len=32,
                        max_prompt_len=8, dp_shards=2)
    eng = Engine(TINY, ecfg, strategy=BASELINE, mesh=mesh, params=params)
    usable = eng.layout.n_pages - eng.layout.n_shards
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in ([1, 2, 3, 4, 5], [7, 8, 9], [11, 12], [2, 4, 6])]
    while eng.step():
        s = eng.stats()
        assert s["free_pages"] + s["pages_in_use"] == usable
    assert all(r.finished for r in reqs)
    s = eng.stats()
    assert s["pages_in_use"] == 0 and s["free_pages"] == usable


def test_ttft_stamped_at_submit_not_construction():
    """A router may hold a Request before handing it to an engine; that
    hold must not be folded into the engine's queue-wait.  t_submit is
    stamped by Scheduler.submit, t_created at construction."""
    import time as _time
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=9)
    sched = Scheduler(PageAllocator(2, layout), max_prompt_len=8)
    req = Request(prompt=[1, 2, 3], max_new_tokens=2)
    assert req.t_submit is None
    _time.sleep(0.02)                       # the router hold
    sched.submit(req)
    assert req.t_submit is not None
    assert req.t_submit - req.t_created >= 0.015


def test_ttft_excludes_pre_submit_hold_on_engine():
    params = _weights(TINY)
    eng = Engine(TINY, ECFG, params=params)
    import time as _time
    req = Request(prompt=[1, 2, 3], max_new_tokens=2)
    _time.sleep(0.02)
    eng.scheduler.submit(req)
    eng.run()
    assert req.finished
    # engine-side TTFT excludes the hold; end-to-end TTFT includes it
    assert req.ttft_e2e - req.ttft >= 0.015


def test_stream_raises_on_foreign_request():
    """Streaming a request the engine does not own must raise a
    structured StreamError, not silently end the iterator."""
    from repro.serve import StreamError
    params = _weights(TINY)
    a = Engine(TINY, ECFG, params=params)
    b = Engine(TINY, ECFG, params=params)
    req = a.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(StreamError) as exc:
        list(b.stream(req))
    assert exc.value.errors[0]["code"] == "foreign_request"
    assert str(req.rid) in exc.value.errors[0]["message"]
    # the owning engine still serves it fine
    assert len(list(a.stream(req))) == 4 and req.finished


def test_admit_early_break_skips_queue_rescan(monkeypatch):
    """When no shard can fit even the smallest waiting request, the
    admission pass stops after the head instead of rescanning the whole
    backlog every tick (first-fit order preserved)."""
    layout = PagedLayout(page_size=4, pages_per_slot=4, n_pages=5)
    alloc = PageAllocator(4, layout)        # 4 usable pages
    sched = Scheduler(alloc, max_prompt_len=8)
    hog = sched.submit(Request(prompt=[1] * 8, max_new_tokens=8))
    assert sched.admit() == [hog]           # reserves all 4 pages
    waiting = [sched.submit(Request(prompt=[1] * 4, max_new_tokens=4))
               for _ in range(10)]
    calls = []
    orig = alloc.can_admit
    monkeypatch.setattr(
        alloc, "can_admit",
        lambda *a: (calls.append(a), orig(*a))[1])
    assert sched.admit() == []
    assert len(calls) == 1, "pass must break once nothing can fit"
    assert list(sched.waiting) == waiting   # order untouched
    # pages free up -> the same queue admits again, first-fit
    sched.finish(hog)
    admitted = sched.admit()
    assert admitted and admitted[0] is waiting[0]
