"""Paged KV-cache subsystem: PagePool/PrefixTree bookkeeping, the
Pallas paged flash-decode kernel vs its jnp oracle, paged scheduler
serving (token equivalence, shared-page refcounts, COW, preemption,
budget/drain properties) and the page-size-aware planner."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from helpers.hypothesis_compat import given, settings, st

from repro.checkpoint import load_manifest, partition_and_save
from repro.configs import get_config
from repro.core import BatchScheduler, Hermes, PipeloadEngine
from repro.core.engine import _Ledger
from repro.core.kv_pages import (BlockTable, PagePool, PrefixTree,
                                 pages_for)
from repro.core.planner import plan_generate
from repro.kernels import ops, ref
from repro.models.api import build_model

MAX_TOTAL = 16


@pytest.fixture(scope="module")
def gpt2s(tmp_path_factory):
    """Small-but-real GPT-2-geometry checkpoint on disk."""
    cfg = get_config("gpt2_base").with_(
        num_layers=3, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=300, vocab_pad_to=4, remat=False)
    path = tmp_path_factory.mktemp("ckpt") / "gpt2s"
    api = build_model(cfg)
    partition_and_save(api.init(jax.random.PRNGKey(0)), cfg, path)
    return cfg, path


def _mem(path, cfg):
    man = load_manifest(path)
    layer_b = man["layer_bytes"] // cfg.num_layers
    other = man["total_bytes"] - man["layer_bytes"]
    return layer_b, other


# ---------------------------------------------------------------------------
# PagePool bookkeeping
# ---------------------------------------------------------------------------
def test_pool_alloc_share_release_ledger_exact():
    led = _Ledger(None)
    pool = PagePool(4, 100, led)
    a, b = pool.alloc(), pool.alloc()
    assert led.resident == 200 and pool.mapped_bytes == 200
    pool.share(a)                       # refcount bump: no new bytes
    assert led.resident == 200
    assert not pool.release(a)          # sibling still holds it
    assert led.resident == 200
    assert pool.release(a)              # last reference -> freed
    assert led.resident == 100
    assert pool.release(b)
    assert led.resident == 0 and pool.mapped_pages == 0


def test_pool_free_list_reuse_keeps_high_water():
    pool = PagePool(4, 1)
    pids = [pool.alloc() for _ in range(5)]
    for p in pids:
        pool.release(p)
    again = [pool.alloc() for _ in range(5)]
    assert sorted(again) == sorted(pids)       # recycled, not grown
    assert pool.capacity == 5                  # high-water mark
    assert pool.stats.reuses == 5


def test_pool_errors():
    pool = PagePool(4, 1)
    with pytest.raises(KeyError):
        pool.release(0)
    with pytest.raises(KeyError):
        pool.share(7)
    with pytest.raises(ValueError):
        PagePool(0, 1)


# ---------------------------------------------------------------------------
# PrefixTree sharing semantics
# ---------------------------------------------------------------------------
def test_tree_full_page_prefix_sharing():
    pool, tree = PagePool(4, 1), PrefixTree(4)
    p1, s1 = tree.insert(list(range(10)), pool)         # 2 full + partial
    assert len(p1) == 3 and s1 == 0
    # same first 8 tokens, different tail: shares the 2 full pages only
    p2, s2 = tree.insert(list(range(8)) + [99, 98], pool)
    assert s2 == 2 and p2[:2] == p1[:2] and p2[2] != p1[2]
    assert pool.refcount(p1[0]) == 2
    # identical prompt: shares ALL pages including the partial one
    p3, s3 = tree.insert(list(range(10)), pool)
    assert s3 == 3 and p3 == p1
    # diverging first page: nothing shared
    p4, s4 = tree.insert([5, 4, 3, 2, 1], pool)
    assert s4 == 0 and not set(p4) & set(p1)


def test_tree_prunes_on_forget_and_drains():
    pool, tree = PagePool(4, 1), PrefixTree(4)
    t1 = BlockTable(*tree.insert(list(range(8)), pool))
    t2 = BlockTable(*tree.insert(list(range(8)), pool))
    assert t2.n_shared == 2
    t1.release_all(pool, tree)
    assert pool.mapped_pages == 2          # t2 still holds both pages
    t2.release_all(pool, tree)
    assert pool.mapped_pages == 0
    # pruned: a new identical prompt re-allocates instead of sharing
    _, s = tree.insert(list(range(8)), pool)
    assert s == 0


def test_cow_release_of_last_reference_must_prune_tree():
    """The scheduler's COW drops one reference on the old shared page;
    if the sibling was preempted mid-COW that drop is the LAST one and
    the tree node must be pruned with it, or a later identical prompt
    would share a recycled page id holding someone else's K/V."""
    pool, tree = PagePool(4, 1), PrefixTree(4)
    t_a = BlockTable(*tree.insert(list(range(4)), pool))
    t_b = BlockTable(*tree.insert(list(range(4)), pool))
    pid = t_a.pages[0]
    assert pool.refcount(pid) == 2
    t_b.release_all(pool, tree)            # sibling preempted mid-COW
    # A's COW now drops the LAST reference — scheduler must forget(pid)
    if pool.release(pid):
        tree.forget(pid)
    t_a.pages[0] = pool.alloc()            # the private COW copy
    # a newcomer with the same prompt must NOT hit the stale node
    pids, shared = tree.insert(list(range(4)), pool)
    assert shared == 0 and pool.refcount(pids[0]) == 1


def test_pages_for():
    assert pages_for(0, 4) == 0
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


# ---------------------------------------------------------------------------
# property: any alloc/share/free interleaving keeps the ledger exact,
# never overruns the accounted budget, drains to zero, and the pool
# plateaus at its high-water mark
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_reqs=st.integers(1, 6),
       page_size=st.sampled_from([1, 2, 4]))
def test_pool_interleaving_property(seed, n_reqs, page_size):
    rng = np.random.default_rng(seed)
    led = _Ledger(None)
    pool = PagePool(page_size, 10, led)
    tree = PrefixTree(page_size)
    live = {}
    hw = 0
    for step in range(40):
        assert led.resident == pool.mapped_bytes       # ledger exact
        op = rng.integers(0, 3)
        if op == 0 and len(live) < n_reqs:             # admit
            toks = rng.integers(0, 3, rng.integers(1, 10)).tolist()
            live[step] = BlockTable(*tree.insert(toks, pool))
        elif op == 1 and live:                          # grow one page
            t = live[rng.choice(list(live))]
            t.pages.append(pool.alloc())
        elif op == 2 and live:                          # retire
            k = rng.choice(list(live))
            live.pop(k).release_all(pool, tree)
        # high-water sampled AFTER the op, so pages the last step maps
        # count toward the final capacity check
        hw = max(hw, pool.mapped_pages)
        assert pool.capacity <= hw
    for t in list(live.values()):
        t.release_all(pool, tree)
    assert pool.mapped_pages == 0 and led.resident == 0  # exact drain
    assert pool.capacity == hw


# ---------------------------------------------------------------------------
# Pallas paged kernel == jnp oracle across a (page, seq) sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("page,nb", [(4, 3), (8, 4), (16, 2), (64, 2)])
def test_paged_kernel_matches_oracle(page, nb):
    rng = np.random.default_rng(page * 100 + nb)
    b, kv, g, dh, n_pages = 3, 2, 2, 32, 2 * nb + 3
    kp = jnp.asarray(rng.normal(size=(n_pages, page, kv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, page, kv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, n_pages, (b, nb)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, nb * page + 1, (b,)), jnp.int32)
    out = ops.paged_decode(q, kp, vp, tables, lengths)
    exp = ref.paged_decode_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), page=st.sampled_from([2, 4, 8]),
       nb=st.integers(1, 4))
def test_paged_kernel_property(seed, page, nb):
    rng = np.random.default_rng(seed)
    b, dh = int(rng.integers(1, 4)), 16
    kv, g = int(rng.integers(1, 3)), int(rng.integers(1, 3))
    n_pages = nb + int(rng.integers(1, 4))
    kp = jnp.asarray(rng.normal(size=(n_pages, page, kv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, page, kv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, n_pages, (b, nb)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, nb * page + 1, (b,)), jnp.int32)
    out = ops.paged_decode(q, kp, vp, tables, lengths)
    exp = ref.paged_decode_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged serving == dense serving, token for token
# ---------------------------------------------------------------------------
def _serve(path, cfg, prompts, news, *, page_size=None, budget=None,
           max_inflight=4, prefix_cache=True, seed=None, pin=0):
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         budget_bytes=budget, pin_window=pin,
                         page_size=page_size)
    sched = BatchScheduler(eng, max_inflight=max_inflight,
                           max_total_len=MAX_TOTAL,
                           prefix_cache=prefix_cache, seed=seed)
    rids = [sched.submit(p, n) for p, n in zip(prompts, news)]
    outs, stats = sched.run()
    return sched, rids, outs, stats


def test_paged_equals_dense_shared_prefixes(gpt2s):
    cfg, path = gpt2s
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 300, (8,))
    prompts = [np.concatenate([shared, rng.integers(0, 300, (4,))])
               for _ in range(3)]
    news = [4, 2, 3]
    _, rd, outs_d, st_d = _serve(path, cfg, prompts, news)
    s, rp, outs_p, st_p = _serve(path, cfg, prompts, news, page_size=4)
    for a, b in zip(rp, rd):
        np.testing.assert_array_equal(outs_p[a], outs_d[b])
    assert st_p.prefix_hit_pages > 0            # the shared prompt hit
    assert st_p.cache_bytes_peak < st_d.cache_bytes_peak
    assert s.pool.mapped_pages == 0             # drained


def test_paged_equals_sequential_odd_page_size(gpt2s):
    """Page size that does NOT divide max_total_len still decodes the
    right tokens (the gathered cache is just padded a little longer)."""
    cfg, path = gpt2s
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 300, (7,)) for _ in range(2)]
    refs = []
    for p in prompts:
        eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2)
        out, _ = eng.run_generate(p[None], 4, kv_cache=True)
        refs.append(np.asarray(out)[0])
    _, rids, outs, _ = _serve(path, cfg, prompts, [4, 4], page_size=5)
    for rid, r in zip(rids, refs):
        np.testing.assert_array_equal(outs[rid], r)


def test_paged_with_pinned_window(gpt2s):
    cfg, path = gpt2s
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 300, (8,)) for _ in range(2)]
    _, rd, outs_d, _ = _serve(path, cfg, prompts, [3, 3])
    _, rp, outs_p, st = _serve(path, cfg, prompts, [3, 3], page_size=4,
                               pin=2)
    for a, b in zip(rp, rd):
        np.testing.assert_array_equal(outs_p[a], outs_d[b])


def test_paged_equals_dense_mla(tmp_path):
    """MLA caches ({c, kr} latent leaves) ride the generic
    gather -> layer_decode -> scatter path."""
    cfg = get_config("minicpm3_4b").reduced().with_(
        num_layers=2, vocab_size=300, vocab_pad_to=4)
    assert cfg.attention == "mla"
    path = tmp_path / "mla"
    api = build_model(cfg)
    partition_and_save(api.init(jax.random.PRNGKey(0)), cfg, path)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 300, (6,)) for _ in range(2)]
    _, rd, outs_d, _ = _serve(path, cfg, prompts, [3, 3])
    _, rp, outs_p, _ = _serve(path, cfg, prompts, [3, 3], page_size=4)
    for a, b in zip(rp, rd):
        np.testing.assert_array_equal(outs_p[a], outs_d[b])


def test_prefix_cache_off_allocates_private_pages(gpt2s):
    cfg, path = gpt2s
    rng = np.random.default_rng(4)
    p = rng.integers(0, 300, (8,))
    _, _, outs_on, st_on = _serve(path, cfg, [p, p], [3, 3], page_size=4)
    _, _, outs_off, st_off = _serve(path, cfg, [p, p], [3, 3], page_size=4,
                                    prefix_cache=False)
    assert st_on.prefix_hit_pages > 0
    assert st_off.prefix_hit_pages == 0
    assert st_off.pages_allocated > st_on.pages_allocated
    for rid in outs_on:
        np.testing.assert_array_equal(outs_on[rid], outs_off[rid])


# ---------------------------------------------------------------------------
# satellite regression: refcounted shared pages are NOT freed while a
# sibling request is still live (page-granular exact drain on retire)
# ---------------------------------------------------------------------------
def test_shared_pages_survive_sibling_retirement(gpt2s):
    cfg, path = gpt2s
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 300, (8,))       # 2 full pages at ps=4
    p1 = np.concatenate([shared, rng.integers(0, 300, (2,))])
    p2 = np.concatenate([shared, rng.integers(0, 300, (2,))])
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         page_size=4)
    sched = BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL)
    r1 = sched.submit(p1, 1)                  # retires after one round
    r2 = sched.submit(p2, 5)                  # keeps decoding
    sched.step()                              # both admitted + prefilled
    sched.step()                              # r1 retires here
    assert r1 in sched.done and r2 not in sched.done
    live = sched.inflight[0].table
    shared_pids = live.pages[:live.n_shared]
    assert shared_pids, "prefix pages should be shared"
    # the retired sibling dropped ITS references; the pages survive
    for pid in shared_pids:
        assert sched.pool.refcount(pid) == 1
    # and the survivor keeps decoding the same tokens as a solo run
    while sched.step():
        pass
    eng2 = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2)
    ref_out, _ = eng2.run_generate(p2[None], 5, kv_cache=True)
    np.testing.assert_array_equal(sched.done[r2].tokens,
                                  np.asarray(ref_out)[0])
    assert sched.pool.mapped_pages == 0       # full drain at the end


def test_cow_on_identical_prompts(gpt2s):
    """Two identical prompts share even the partial last page; the
    first divergent decode write must copy-on-write, not clobber."""
    cfg, path = gpt2s
    rng = np.random.default_rng(6)
    p = rng.integers(0, 300, (10,))           # 2 full + 1 partial page
    s, rids, outs, st = _serve(path, cfg, [p, p], [4, 4], page_size=4)
    assert st.cow_copies >= 1
    np.testing.assert_array_equal(outs[rids[0]], outs[rids[1]])
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2)
    ref_out, _ = eng.run_generate(p[None], 4, kv_cache=True)
    np.testing.assert_array_equal(outs[rids[0]], np.asarray(ref_out)[0])


# ---------------------------------------------------------------------------
# budget: paged admission floor, growth preemption, exact drain
# ---------------------------------------------------------------------------
def test_paged_admits_more_than_dense_at_same_budget(gpt2s):
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    per_req = cfg.num_layers * cfg.cache_bytes(1, MAX_TOTAL)
    # one streaming layer + 2.5 dense caches: dense admits 2, pages fit 3
    budget = other + layer_b + int(2.5 * per_req)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 300, (12,))          # 3 shared pages of 4
    prompts = [np.concatenate([shared, rng.integers(0, 300, (1,))])
               for _ in range(4)]
    news = [3] * 4
    _, _, outs_d, st_d = _serve(path, cfg, prompts, news, budget=budget)
    _, _, outs_p, st_p = _serve(path, cfg, prompts, news, budget=budget,
                                page_size=4)
    assert st_d.max_inflight_seen == 2
    assert st_p.max_inflight_seen > st_d.max_inflight_seen
    assert st_p.peak_bytes <= budget
    for rid in outs_d:
        np.testing.assert_array_equal(outs_p[rid], outs_d[rid])


def test_growth_preemption_recovers_and_finishes(gpt2s):
    """Admission lets several short-prompt requests in, but their decode
    growth outruns the budget: the youngest is preempted, re-queued and
    finished later — nobody deadlocks, everyone gets every token."""
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    ps = 4
    page_b = cfg.num_layers * cfg.cache_bytes(1, ps)
    # room for EXACTLY 7 pages above one streaming layer: three 1-page
    # prompts admit (3 mapped + 3 headroom), but each grows to 4 pages
    # (16 tokens) over decode — 12 > 7 forces preemption
    budget = other + 7 * page_b + layer_b
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 300, (4,)) for _ in range(3)]
    news = [12] * 3
    s, rids, outs, st = _serve(path, cfg, prompts, news, budget=budget,
                               page_size=ps, max_inflight=3)
    assert st.requests == 3
    for i, rid in enumerate(rids):
        assert len(outs[rid]) == 4 + news[i]
    assert st.preemptions >= 1
    assert st.peak_bytes <= budget
    assert s.pool.mapped_pages == 0


def test_preemption_victim_is_youngest_even_when_growing(gpt2s):
    """Strict age order: when growth cannot clear the floor, the
    YOUNGEST request is bounced — even if it is the one growing — and
    the oldest is never preempted."""
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    ps = 4
    page_b = cfg.num_layers * cfg.cache_bytes(1, ps)
    budget = other + 6 * page_b + layer_b
    rng = np.random.default_rng(13)
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         budget_bytes=budget, page_size=ps)
    sched = BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL)
    r_old = sched.submit(rng.integers(0, 300, (4,)), 12)
    r_new = sched.submit(rng.integers(0, 300, (4,)), 12, arrival_round=1)
    outs, st = sched.run()
    assert st.requests == 2
    assert all(len(outs[r]) == 16 for r in (r_old, r_new))
    preempted = {e[2] for e in st.event_log(["preempt"])}
    assert preempted == {f"req{r_new}"}       # never the oldest
    assert st.peak_bytes <= budget


def test_submit_rejects_budget_without_admission_headroom(gpt2s):
    """A budget fitting a request's pages EXACTLY but not the one-page
    admission headroom must be rejected at submit() — accepting it
    would leave the request queued forever (regression: run() used to
    spin)."""
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    ps = 8
    page_b = cfg.num_layers * cfg.cache_bytes(1, ps)
    # prompt 6 + 2 new tokens = 1 page; admission needs 1 + 1 headroom
    budget = other + layer_b + page_b
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         budget_bytes=budget, page_size=ps)
    sched = BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL)
    with pytest.raises(ValueError, match="KV decode floor"):
        sched.submit(np.arange(6), 2)
    # one more page of budget and the same request serves fine
    eng2 = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         budget_bytes=budget + page_b, page_size=ps)
    sched2 = BatchScheduler(eng2, max_inflight=2, max_total_len=MAX_TOTAL)
    rid = sched2.submit(np.arange(6), 2)
    outs, st = sched2.run()
    assert len(outs[rid]) == 8 and st.requests == 1


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), n_reqs=st.integers(1, 4),
       page_size=st.sampled_from([2, 4, 5]),
       cache_pages=st.integers(6, 14),
       share=st.booleans())
def test_paged_serving_property(gpt2s, seed, n_reqs, page_size,
                                cache_pages, share):
    """Random paged workloads under tight budgets: never deadlock,
    never exceed the budget, retire every request with its full token
    count, and drain the pool to zero."""
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    page_b = cfg.num_layers * cfg.cache_bytes(1, page_size)
    need = pages_for(MAX_TOTAL, page_size) + 1
    budget = other + max(cache_pages, need) * page_b + 2 * layer_b
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 300, (6,)) if share else None
    prompts, news = [], []
    for i in range(n_reqs):
        tail = rng.integers(0, 300, (int(rng.integers(1, 5)),))
        p = np.concatenate([shared, tail]) if share else tail
        prompts.append(p)
        news.append(int(rng.integers(1, MAX_TOTAL - len(p) + 1)))
    s, rids, outs, st = _serve(path, cfg, prompts, news, budget=budget,
                               page_size=page_size, max_inflight=3)
    assert st.requests == n_reqs
    for i, rid in enumerate(rids):
        assert len(outs[rid]) == len(prompts[i]) + news[i]
    assert st.peak_bytes <= budget
    assert s.pool.mapped_pages == 0
    assert s.ledger.resident == sum(
        s.engine.shards[a]["bytes"] for a in ("embed", "head"))


# ---------------------------------------------------------------------------
# engine: single-request paged accounting lowers the ledger peak
# ---------------------------------------------------------------------------
def test_engine_paged_generate_same_tokens_lower_peak(gpt2s):
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    cache = cfg.num_layers * cfg.cache_bytes(1, 14)
    budget = other + cache + 3 * layer_b
    rng = np.random.default_rng(9)
    p = rng.integers(0, 300, (6,))
    eng_d = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                           budget_bytes=budget)
    out_d, st_d = eng_d.run_generate(p[None], 8, kv_cache=True)
    eng_p = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                           budget_bytes=budget, page_size=2)
    out_p, st_p = eng_p.run_generate(p[None], 8, kv_cache=True)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    assert st_p.cache_bytes <= st_d.cache_bytes
    assert st_p.peak_bytes <= st_d.peak_bytes
    # paged run reserves page-by-page: more cache_reserve events
    assert len(st_p.event_log(["cache_reserve"])) > 1


def test_engine_paged_falls_back_dense_for_expert_split(tmp_path):
    """page_size + expert-split MoE: _bind_expert sizes the ExpertCache
    from ledger headroom at bind time, so incremental page charging
    would hand the decode pages' bytes to the cache and deadlock the
    first growth (regression).  The engine must reserve up front."""
    from repro.models.config import MOE, ModelConfig
    cfg = ModelConfig("moe-paged-test", MOE, 2, 64, 4, 2, 0, 256,
                      head_dim=16, n_experts=4, top_k=2, expert_d_ff=32,
                      dtype="float32", vocab_pad_to=64, remat=False)
    path = tmp_path / "moe"
    partition_and_save(build_model(cfg).init(jax.random.PRNGKey(0)),
                       cfg, path)
    man = load_manifest(path)
    assert man["expert_split"]
    budget = man["total_bytes"] + cfg.num_layers * cfg.cache_bytes(1, 10)
    eng_d = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                           budget_bytes=budget)
    out_d, _ = eng_d.run_generate(np.arange(6)[None], 4, kv_cache=True)
    eng_p = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                           budget_bytes=budget, page_size=2)
    out_p, st = eng_p.run_generate(np.arange(6)[None], 4, kv_cache=True)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    # up-front reservation: exactly ONE cache_reserve event
    assert len(st.event_log(["cache_reserve"])) == 1


def test_engine_paged_budget_floor_still_enforced(gpt2s):
    cfg, path = gpt2s
    layer_b, other = _mem(path, cfg)
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         budget_bytes=other + layer_b, page_size=4)
    with pytest.raises(ValueError, match="KV decode floor"):
        eng.run_generate(np.arange(6)[None], 4, kv_cache=True)


# ---------------------------------------------------------------------------
# scheduler config surface
# ---------------------------------------------------------------------------
def test_seed_recorded_in_serve_stats(gpt2s):
    cfg, path = gpt2s
    rng = np.random.default_rng(10)
    p = rng.integers(0, 300, (6,))
    _, _, _, st = _serve(path, cfg, [p], [2], seed=1234)
    assert st.seed == 1234
    _, _, _, st2 = _serve(path, cfg, [p], [2])
    assert st2.seed is None


def test_scheduler_inherits_engine_page_size(gpt2s):
    cfg, path = gpt2s
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         page_size=4)
    sched = BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL)
    assert sched.page_size == 4 and sched.pool is not None


# ---------------------------------------------------------------------------
# planner: page-size dimension
# ---------------------------------------------------------------------------
def _profile(n_layers=4, layer_b=1000, other=500):
    shards = [{"name": f"L{i}", "kind": "layer", "bytes": layer_b,
               "t_load": 1e-3, "t_comp": 1e-4, "t_decode": 1e-5}
              for i in range(n_layers)]
    return {"num_layers": n_layers, "layer_bytes": layer_b,
            "other_bytes": other, "shards": shards, "seq": 8,
            "quant": None}


def test_planner_paged_admits_more_inflight_with_sharing():
    prof = _profile()
    total, cbl = 32, 32 * 10              # 10 bytes per token per layer
    budget = prof["other_bytes"] + 2 * prof["layer_bytes"] \
        + 4 * 2 * cbl                     # ~2 dense requests' caches
    dense = plan_generate(prof, [budget], new_tokens=8,
                          cache_bytes_per_layer=cbl, max_pin=0,
                          max_inflight=8)[0]
    paged = plan_generate(prof, [budget], new_tokens=8,
                          cache_bytes_per_layer=cbl, max_pin=0,
                          max_inflight=8, page_sizes=(8,), total_len=total,
                          shared_prefix_len=24)[0]
    assert paged.feasible and dense.feasible
    assert paged.page_size == 8
    assert paged.inflight > dense.inflight
    assert paged.cache_bytes < dense.cache_bytes * paged.inflight


def test_planner_page_size_requires_total_len():
    with pytest.raises(ValueError, match="total_len"):
        plan_generate(_profile(), [None], new_tokens=4,
                      cache_bytes_per_layer=100, page_sizes=(8,))


def test_planner_dense_entry_unchanged_without_pages():
    prof = _profile()
    e = plan_generate(prof, [None], new_tokens=4,
                      cache_bytes_per_layer=100)[0]
    assert e.page_size == 0


def test_hermes_scheduler_facade_paged(gpt2s, tmp_path):
    cfg, path = gpt2s
    h = Hermes(path, cfg)
    h.profile(batch=1, seq=8, force=True)
    layer_b, other = _mem(path, cfg)
    page_b = cfg.num_layers * cfg.cache_bytes(1, 4)
    budget = other + 14 * page_b + 3 * layer_b
    sched = h.scheduler(budget_bytes=budget, max_inflight=3,
                        prompt_len=8, new_tokens=4, page_sizes=(4,),
                        shared_prefix_len=8, seed=7)
    assert sched.page_size in (0, 4, None) or sched.page_size == 4
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 300, (8,))
    for _ in range(3):
        sched.submit(shared, 4)
    outs, stats = sched.run()
    assert stats.requests == 3
    assert stats.peak_bytes <= budget
    assert stats.seed == 7


def test_paged_rejects_expert_split(gpt2s):
    cfg, path = gpt2s
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2)
    eng.expert = object()     # simulate an expert-split engine
    with pytest.raises(ValueError, match="expert-split"):
        BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL,
                       page_size=4)
