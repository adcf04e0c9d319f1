"""Serving subsystem certification: paged KV cache + scheduler + engine.

Three layers of coverage, mirroring the repo's kernel-test discipline:

* **allocator** — PageState alloc/free invariants (incl. under ``jit``)
  and property-based scheduler runs (admit/evict/preempt streams drawn by
  hypothesis or the deterministic fallback shim) asserting no page leaks
  or double-frees at every step;
* **kernel** — the Pallas paged-decode attention kernel (interpret mode)
  against the gather-based XLA lowering, over GQA/window/softcap cases;
* **engine** — paged-cache decode is consistent with full-recompute
  generation: per-step logits match the full forward at the same position
  (dense + sparse junctions, both backends) and greedy token-id parity
  over >= 32 steps, including mixed prompt lengths, preemption under a
  tiny page pool, and SSM recurrent state riding the cache interface.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pinned container image: degraded deterministic sweep
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.flash_attention import paged_decode_attention
from repro.launch.serve import generate, generate_cached
from repro.nn import ModelConfig, SparsityConfig, build_model
from repro.serving import EngineConfig, ServingEngine, kv_cache
from repro.serving.scheduler import Request, Scheduler, StepPlan
from repro.serving.spec import propose_drafts


# ---------------------------------------------------------------------------
# configs / oracles
# ---------------------------------------------------------------------------


def _tiny_cfg(sparse: bool = False, **kw) -> ModelConfig:
    sp = SparsityConfig(enabled=sparse, rho_ffn=(0.5, 1.0),
                        block_in=16, block_out=16)
    return ModelConfig(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=256, attn_chunk=16, loss_chunk=16, dtype="float32",
        remat=False, sparsity=sp, **kw)


def _recompute_tokens(model, params, prompt: np.ndarray,
                      steps: int) -> list:
    """Greedy full-recompute oracle: forward over a fixed padded buffer."""
    buf = np.zeros((1, len(prompt) + steps), np.int32)
    buf[0, :len(prompt)] = prompt
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    out, n = [], len(prompt)
    for _ in range(steps):
        h = fwd(params, jnp.asarray(buf))
        tok = int(jnp.argmax(model.logits_fn(params, h[:, n - 1:n])[0, 0]))
        out.append(tok)
        if n < buf.shape[1]:
            buf[0, n] = tok
        n += 1
    return out


def _check_engine_parity(model, params, prompts, steps, ecfg):
    eng = ServingEngine(model, params, ecfg)
    for i, p in enumerate(prompts):
        eng.add_request(p, steps, req_id=i)
    while eng.sched.has_work():
        eng.step()
        eng.sched.check_invariants()
    for i, p in enumerate(prompts):
        ref = _recompute_tokens(model, params, p, steps)
        assert eng.outputs[i].tolist() == ref, \
            f"req {i} (len {len(p)}): {eng.outputs[i].tolist()} != {ref}"
    return eng


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_page_state_alloc_free_roundtrip():
    st_ = kv_cache.init_page_state(slots=3, total_pages=8,
                                   max_pages_per_seq=4)
    st_ = kv_cache.alloc_pages(st_, 0, 3)
    st_ = kv_cache.alloc_pages(st_, 1, 2)
    assert int(st_.free_count) == 3
    table = np.asarray(st_.page_table)
    mapped = table[table >= 0]
    assert len(set(mapped.tolist())) == 5  # no double-mapping
    st_ = kv_cache.free_slot(st_, 0)
    assert int(st_.free_count) == 6
    assert (np.asarray(st_.page_table[0]) == -1).all()
    # freed ids are allocatable again and still unique
    st_ = kv_cache.alloc_pages(st_, 2, 4)
    table = np.asarray(st_.page_table)
    mapped = table[table >= 0]
    assert len(set(mapped.tolist())) == len(mapped) == 6


def test_page_state_ops_work_under_jit():
    st_ = kv_cache.init_page_state(slots=2, total_pages=6,
                                   max_pages_per_seq=3)
    alloc2 = jax.jit(lambda s, slot: kv_cache.alloc_pages(s, slot, 2))
    free = jax.jit(kv_cache.free_slot)
    st_ = alloc2(st_, jnp.asarray(0))
    st_ = alloc2(st_, jnp.asarray(1))
    assert int(st_.free_count) == 2
    st_ = free(st_, jnp.asarray(0))
    assert int(st_.free_count) == 4
    ids = np.asarray(st_.free_stack)[:4]
    assert len(set(ids.tolist())) == 4


def test_physical_addresses_redirect_invalid_to_trash():
    table = jnp.asarray([[2, 0, -1, -1]], jnp.int32)
    pos = jnp.asarray([[0, 3, 4, 9]], jnp.int32)   # page size 4
    valid = jnp.asarray([[True, True, True, False]])
    phys, off = kv_cache.physical_addresses(table, pos, valid,
                                            page_size=4, trash_page=7)
    # last entry: invalid row -> trash; pos 9 maps an unmapped (-1) page,
    # which must also redirect to trash rather than index page -1
    assert phys.tolist() == [[2, 2, 0, 7]]
    assert off.tolist() == [[0, 3, 0, 1]]


def test_truncate_releases_tail_pages():
    """Unit: rolling back tokens frees exactly the pages left with no
    live token, reverts their table entries, and keeps the rest."""
    st_ = kv_cache.init_page_state(slots=2, total_pages=8,
                                   max_pages_per_seq=4)
    st_ = kv_cache.alloc_pages(st_, 0, 3)          # room for 12 tokens
    st_ = kv_cache.advance(st_, 0, 9)              # 9 written (3 pages)
    st_ = kv_cache.truncate(st_, 0, 5, page_size=4)
    assert int(st_.seq_lens[0]) == 4               # 1 page still live
    assert int(st_.n_pages[0]) == 1
    assert int(st_.free_count) == 7
    row = np.asarray(st_.page_table[0])
    assert (row[1:] == -1).all() and row[0] >= 0
    # freed ids are unique and allocatable again
    ids = np.asarray(st_.free_stack)[:7]
    assert len(set(ids.tolist())) == 7
    # full rollback empties the slot
    st_ = kv_cache.truncate(st_, 0, 4, page_size=4)
    assert int(st_.n_pages[0]) == 0
    assert int(st_.free_count) == 8
    assert (np.asarray(st_.page_table[0]) == -1).all()


def test_truncate_respects_reclaimed_prefix():
    """Truncate after sliding-window reclamation: tail pages free, the
    (already-released) prefix stays untouched and first_page holds."""
    st_ = kv_cache.init_page_state(slots=1, total_pages=8,
                                   max_pages_per_seq=6)
    st_ = kv_cache.alloc_pages(st_, 0, 4)
    st_ = kv_cache.advance(st_, 0, 14)             # pages 0..3, ps=4
    st_ = kv_cache.release_prefix(st_, 0, 2)       # window reclaimed 0,1
    assert int(st_.first_page[0]) == 2
    st_ = kv_cache.truncate(st_, 0, 5, page_size=4)  # 14 -> 9 tokens
    assert int(st_.seq_lens[0]) == 9               # page 2 holds 8..11
    assert int(st_.first_page[0]) == 2
    assert int(st_.n_pages[0]) == 1                # page 3 released
    assert int(st_.free_count) == 7


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=500))
def test_truncate_page_accounting_property(seed):
    """Random alloc/advance/truncate/free streams against a host-side
    mirror: no page leaks, no double-maps/frees, tail release exact —
    the allocator-level certification of speculative rollback."""
    rng = np.random.default_rng(seed)
    slots, total, ps, maxp = 3, 10, 4, 5
    st_ = kv_cache.init_page_state(slots, total, maxp)
    n_pages = [0] * slots
    seq = [0] * slots
    free = total
    for _ in range(60):
        slot = int(rng.integers(slots))
        op = ["alloc", "advance", "truncate", "free"][int(rng.integers(4))]
        if op == "alloc":
            n = int(rng.integers(0, min(maxp - n_pages[slot], free) + 1))
            st_ = kv_cache.alloc_pages(st_, slot, n)
            n_pages[slot] += n
            free -= n
        elif op == "advance":
            n = int(rng.integers(0, n_pages[slot] * ps - seq[slot] + 1))
            st_ = kv_cache.advance(st_, slot, n)
            seq[slot] += n
        elif op == "truncate":
            n = int(rng.integers(0, seq[slot] + 1))
            st_ = kv_cache.truncate(st_, slot, n, ps)
            if n:
                seq[slot] -= n
                keep = min(-(-seq[slot] // ps), n_pages[slot])
                free += n_pages[slot] - keep
                n_pages[slot] = keep
        else:
            st_ = kv_cache.free_slot(st_, slot)
            free += n_pages[slot]
            n_pages[slot] = 0
            seq[slot] = 0
        assert int(st_.free_count) == free
        assert list(np.asarray(st_.n_pages)) == n_pages
        assert list(np.asarray(st_.seq_lens)) == seq
        table = np.asarray(st_.page_table)
        mapped = table[table >= 0].tolist()
        assert len(set(mapped)) == len(mapped) == sum(n_pages)
        stack_ids = set(np.asarray(st_.free_stack)[:free].tolist())
        assert len(stack_ids) == free, "duplicate ids on the free stack"
        assert not stack_ids & set(mapped), "page both free and mapped"
    # drain everything: the whole pool must come back exactly once
    for slot in range(slots):
        st_ = kv_cache.free_slot(st_, slot)
    assert int(st_.free_count) == total
    assert set(np.asarray(st_.free_stack).tolist()) == set(range(total))


# ---------------------------------------------------------------------------
# prompt-lookup drafter
# ---------------------------------------------------------------------------


def test_prompt_lookup_drafter_continues_periodic_runs():
    # periodic sequence: the 3-gram suffix recurs, drafts continue it
    assert propose_drafts([1, 2, 3, 1, 2, 3, 1, 2], 3) == [3, 1, 2]
    # most RECENT earlier occurrence wins
    assert propose_drafts([7, 5, 9, 5, 8, 5], 2,
                          max_ngram=1) == [8, 5]
    # falls back to shorter n-grams when the long suffix never recurred
    assert propose_drafts([1, 2, 9, 3, 9], 2) == [3, 9]
    # fewer than k tokens may follow the match
    assert propose_drafts([9, 9, 9, 9], 2) == [9]
    # no match / degenerate inputs -> no drafts, never an exception
    assert propose_drafts([5, 6, 7], 2) == []
    assert propose_drafts([5], 3) == []
    assert propose_drafts([1, 2, 3], 0) == []


# ---------------------------------------------------------------------------
# scheduler properties
# ---------------------------------------------------------------------------


@st.composite
def scheduler_cases(draw):
    slots = draw(st.integers(min_value=1, max_value=3))
    total_pages = draw(st.integers(min_value=2, max_value=10))
    page_size = draw(st.sampled_from([2, 4]))
    max_pages = draw(st.integers(min_value=2, max_value=6))
    budget = draw(st.integers(min_value=1, max_value=12))
    chunk = draw(st.sampled_from([2, 4, 8]))
    n_reqs = draw(st.integers(min_value=1, max_value=6))
    seed = draw(st.integers(min_value=0, max_value=100))
    return slots, total_pages, page_size, max_pages, budget, chunk, \
        n_reqs, seed


@settings(max_examples=30, deadline=None)
@given(scheduler_cases())
def test_scheduler_no_page_leaks_across_admit_evict_preempt(case):
    """Drive the scheduler exactly as the engine does (without a model)
    through random request streams on tiny pools — forcing admissions,
    evictions and recompute-preemptions — and assert the page-pool
    invariants (no leaks, no double-frees/maps) after every step."""
    slots, total_pages, page_size, max_pages, budget, chunk, n_reqs, seed \
        = case
    rng = np.random.default_rng(seed)
    cap = min(max_pages, total_pages) * page_size
    sched = Scheduler(slots=slots, total_pages=total_pages,
                      page_size=page_size, max_pages_per_seq=max_pages,
                      token_budget=budget, prefill_chunk=chunk)
    for i in range(n_reqs):
        plen = int(rng.integers(1, max(2, cap - 1)))
        gen = int(rng.integers(1, max(2, cap - plen)))
        sched.add(Request(req_id=i, prompt=rng.integers(0, 99, plen),
                          max_new_tokens=gen))
    for _ in range(500):
        if not sched.has_work():
            break
        plan = sched.schedule()
        sched.check_invariants()
        for slot, start, toks in plan.prefills:
            seq = sched.active[slot]
            assert start == seq.n_prefilled
            sched.advance_prefill(slot, len(toks))
            if not seq.prefilling and len(seq.tokens) == seq.n_prefilled:
                sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in plan.decode_slots:
            sched.note_decoded(slot)
            sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in range(slots):
            seq = sched.active[slot]
            if seq is not None and seq.done:
                sched.finish(slot)
        sched.check_invariants()
        if plan.n_tokens == 0 and not plan.admitted:
            break  # pool too small for any resident sequence
    sched.check_invariants()
    # every page must be back on the free list once all slots drain
    if not any(s is not None for s in sched.active) and not sched.waiting:
        assert sched.state.free() == total_pages


@settings(max_examples=20, deadline=None)
@given(scheduler_cases())
def test_windowed_scheduler_reclaims_without_leaks_or_double_frees(case):
    """The sliding-window reclamation property test: same random driver
    as above but with a window installed — ``check_invariants`` now also
    asserts no live (in-window) page is ever reclaimed, and the pool must
    still fully drain (every reclaimed page returned exactly once)."""
    slots, total_pages, page_size, max_pages, budget, chunk, n_reqs, seed \
        = case
    rng = np.random.default_rng(seed)
    window = int(rng.integers(1, 2 * page_size + 1))
    cap = min(max_pages, total_pages) * page_size
    sched = Scheduler(slots=slots, total_pages=total_pages,
                      page_size=page_size, max_pages_per_seq=max_pages,
                      token_budget=budget, prefill_chunk=chunk,
                      window=window)
    for i in range(n_reqs):
        plen = int(rng.integers(1, max(2, cap - 1)))
        gen = int(rng.integers(1, max(2, cap - plen)))
        sched.add(Request(req_id=i, prompt=rng.integers(0, 99, plen),
                          max_new_tokens=gen))
    for _ in range(500):
        if not sched.has_work():
            break
        plan = sched.schedule()
        sched.check_invariants()
        for slot, start, toks in plan.prefills:
            seq = sched.active[slot]
            sched.advance_prefill(slot, len(toks))
            sched.check_invariants()
            if not seq.prefilling and len(seq.tokens) == seq.n_prefilled:
                sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in plan.decode_slots:
            sched.note_decoded(slot)
            sched.check_invariants()
            sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in range(slots):
            seq = sched.active[slot]
            if seq is not None and seq.done:
                sched.finish(slot)
        sched.check_invariants()
        if plan.n_tokens == 0 and not plan.admitted:
            break
    sched.check_invariants()
    if not any(s is not None for s in sched.active) and not sched.waiting:
        assert sched.state.free() == total_pages


@settings(max_examples=20, deadline=None)
@given(scheduler_cases())
def test_scheduler_spec_rollback_no_leaks(case):
    """The speculative property test: same random driver, but decode
    slots carry random drafts and the driver accepts a random prefix
    (mimicking greedy verification), exercising note_verified's
    advance + truncate + (optionally window-)reclaim path. Page
    invariants must hold after every step and the pool must drain."""
    slots, total_pages, page_size, max_pages, budget, chunk, n_reqs, seed \
        = case
    rng = np.random.default_rng(seed)
    window = int(rng.integers(1, 2 * page_size + 1)) \
        if seed % 2 else None
    spec_k = int(rng.integers(1, 5))

    def random_drafter(tokens, k):
        return [int(t) for t in rng.integers(0, 99, k)]

    cap = min(max_pages, total_pages) * page_size
    sched = Scheduler(slots=slots, total_pages=total_pages,
                      page_size=page_size, max_pages_per_seq=max_pages,
                      token_budget=budget, prefill_chunk=chunk,
                      window=window, spec_k=spec_k,
                      drafter=random_drafter)
    for i in range(n_reqs):
        plen = int(rng.integers(1, max(2, cap - 1)))
        gen = int(rng.integers(1, max(2, cap - plen)))
        sched.add(Request(req_id=i, prompt=rng.integers(0, 99, plen),
                          max_new_tokens=gen))
    for _ in range(500):
        if not sched.has_work():
            break
        plan = sched.schedule()
        sched.check_invariants()
        for slot, start, toks in plan.prefills:
            seq = sched.active[slot]
            sched.advance_prefill(slot, len(toks))
            if not seq.prefilling and len(seq.tokens) == seq.n_prefilled:
                sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in plan.decode_slots:
            drafts = plan.drafts.get(slot, [])
            m = int(rng.integers(0, len(drafts) + 1))
            sched.note_verified(slot, n_written=1 + len(drafts),
                                n_accepted=1 + m)
            sched.check_invariants()
            for _ in range(1 + m):
                sched.append_token(slot, int(rng.integers(0, 99)))
        for slot in range(slots):
            seq = sched.active[slot]
            if seq is not None and seq.done:
                sched.finish(slot)
        sched.check_invariants()
        if plan.n_tokens == 0 and not plan.admitted:
            break
    sched.check_invariants()
    if not any(s is not None for s in sched.active) and not sched.waiting:
        assert sched.state.free() == total_pages


def test_scheduler_skips_zero_page_victims():
    """Regression: ``_youngest_victim`` could select a sequence admitted
    earlier in the SAME ``schedule()`` call — zero pages allocated — so
    ``_try_alloc`` evicted and re-queued it while freeing nothing. Two
    decoders at a page boundary + one fresh admission force the case."""
    sched = Scheduler(slots=3, total_pages=3, page_size=2,
                      max_pages_per_seq=3, token_budget=8,
                      prefill_chunk=8)
    for i in (0, 1):
        sched.add(Request(req_id=i, prompt=np.asarray([1, 2], np.int32),
                          max_new_tokens=4))
    plan = sched.schedule()
    for slot, start, toks in plan.prefills:
        sched.advance_prefill(slot, len(toks))
        seq = sched.active[slot]
        if not seq.prefilling and len(seq.tokens) == seq.n_prefilled:
            sched.append_token(slot, 7)
    sched.check_invariants()
    # both residents decode next step and need a fresh page (boundary);
    # one free page remains, so the younger decoder's allocation fails
    # with the just-admitted (zero-page) request as the youngest resident
    sched.add(Request(req_id=2, prompt=np.asarray([5, 6], np.int32),
                      max_new_tokens=1))
    plan2 = sched.schedule()
    assert plan2.admitted == [2]
    # pre-fix: slot 2 was evicted (freeing zero pages) and re-queued,
    # leaving the slot empty and the pool no better off
    assert sched.active[2] is not None, \
        "zero-page victim was preempted (freed nothing)"
    assert 2 not in plan2.preempted
    assert plan2.decode_slots == [0]   # the younger decoder just waits
    # slot 2's own prefill then preempts the page-OWNING decoder (slot
    # 1) — a legitimate eviction that actually frees a page
    assert plan2.preempted == [1]
    sched.check_invariants()


def test_scheduler_packs_equal_length_prefill_groups():
    """Equal-length power-of-two chunks from different sequences land in
    one batched group; unequal lengths stay separate (rectangular rows
    are required by the SSM full-scan path)."""
    sched = Scheduler(slots=4, total_pages=32, page_size=4,
                      max_pages_per_seq=8, token_budget=32,
                      prefill_chunk=8)
    for i, plen in enumerate((8, 8, 8, 3)):
        sched.add(Request(
            req_id=i, prompt=np.arange(plen, dtype=np.int32),
            max_new_tokens=1))
    plan = sched.schedule()
    groups = plan.prefill_groups
    by_len = {len(g[0][2]): sorted(item[0] for item in g) for g in groups}
    assert by_len[8] == [0, 1, 2]   # three chunks -> ONE batched call
    assert by_len[2] == [3]         # pow2 chunk of the length-3 prompt
    assert plan.n_tokens == 26


def test_windowed_page_occupancy_stays_bounded():
    """A long decode against a small window holds O(window) pages, not
    O(seq_len): the reclamation actually frees the out-of-window prefix."""
    page_size, window = 4, 8
    sched = Scheduler(slots=1, total_pages=64, page_size=page_size,
                      max_pages_per_seq=64, token_budget=4,
                      prefill_chunk=4, window=window)
    sched.add(Request(req_id=0, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=120))
    steps = 0
    max_resident = 0
    while sched.has_work() and steps < 400:
        plan = sched.schedule()
        for slot, start, toks in plan.prefills:
            sched.advance_prefill(slot, len(toks))
            seq = sched.active[slot]
            if not seq.prefilling and len(seq.tokens) == seq.n_prefilled:
                sched.append_token(slot, 1)
        for slot in plan.decode_slots:
            sched.note_decoded(slot)
            sched.append_token(slot, 1)
        if sched.active[0] is not None:
            max_resident = max(max_resident, sched._n_pages[0])
        for slot in range(1):
            seq = sched.active[slot]
            if seq is not None and seq.done:
                sched.finish(slot)
        sched.check_invariants()
        steps += 1
    assert not sched.has_work()
    assert sched.stats["reclaimed_pages"] > 20
    # window w spans at most ceil(w/page)+1 pages, +1 for the write head
    assert max_resident <= window // page_size + 2
    assert sched.state.free() == 64


def test_engine_sliding_window_reclamation_token_parity():
    """An all-local (fixed-window) model serves through the engine with
    window reclamation active, and stays token-identical to the
    full-recompute oracle while actually freeing out-of-window pages."""
    cfg = _tiny_cfg(sparse=False, layer_pattern=("local",), attn_window=6)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 4)]
    eng = _check_engine_parity(
        model, params, prompts, 24,
        EngineConfig(max_slots=2, page_size=4, total_pages=16,
                     max_pages_per_seq=16, token_budget=12,
                     prefill_chunk=8, backend="xla"))
    assert eng.sched.window == 6
    assert eng.sched.stats["reclaimed_pages"] > 0


def test_engine_reclaim_window_disabled_for_global_layers():
    """Any global (unwindowed) attention layer shares the page table, so
    reclamation must stay off — its pages are live forever."""
    from repro.serving.engine import ServingEngine as SE
    cfg = _tiny_cfg(local_global_ratio=1, attn_window=8)
    assert SE._reclaim_window(cfg) is None
    cfg2 = _tiny_cfg(layer_pattern=("local",), attn_window=8)
    assert SE._reclaim_window(cfg2) == 8


# ---------------------------------------------------------------------------
# paged decode kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,softcap", [(None, None), (6, None),
                                            (None, 30.0), (6, 30.0)])
def test_paged_decode_kernel_interpret_matches_xla(window, softcap):
    rng = np.random.default_rng(0)
    b, hkv, g, dh, page, n_pages, total = 3, 2, 3, 16, 4, 5, 12
    q = jnp.asarray(rng.normal(size=(b, hkv, g, dh)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(total, page, hkv, dh)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(total, page, hkv, dh)),
                          jnp.float32)
    # rows with different lengths; unmapped tail entries are -1
    table = np.full((b, n_pages), -1, np.int32)
    perm = rng.permutation(total - 1)  # page `total-1` plays trash
    lengths = np.asarray([3, 11, 17], np.int32)
    lengths = np.minimum(lengths, n_pages * page)
    k = 0
    for i in range(b):
        for pg in range(-(-int(lengths[i]) // page)):
            table[i, pg] = perm[k]
            k += 1
    ref = paged_decode_attention(q, k_pages, v_pages,
                                 jnp.asarray(table), jnp.asarray(lengths),
                                 window=window, softcap=softcap,
                                 backend="xla")
    out = paged_decode_attention(q, k_pages, v_pages,
                                 jnp.asarray(table), jnp.asarray(lengths),
                                 window=window, softcap=softcap,
                                 backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged decode == full recompute (logits + tokens)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("backend,interp", [("xla", False),
                                            ("pallas", True)])
def test_paged_decode_logits_match_full_forward(sparse, backend, interp):
    """Chunked paged prefill + paged decode reproduce the full-recompute
    forward's last-token logits at every step (model-level, no engine)."""
    cfg = _tiny_cfg(sparse=sparse)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    page_size, n_prompt, n_decode = 4, 10, 5
    toks = rng.integers(0, cfg.vocab_size,
                        n_prompt + n_decode).astype(np.int32)

    total_pages = -(-(n_prompt + n_decode) // page_size)
    st_ = kv_cache.init_page_state(1, total_pages, total_pages)
    st_ = kv_cache.alloc_pages(st_, 0, total_pages)
    cache = model.stack.init_paged_cache(1, total_pages, page_size,
                                         jnp.float32)

    def paged(tokens_chunk, pos):
        return model.paged_step(
            params, jnp.asarray(tokens_chunk[None]),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray([len(tokens_chunk)], jnp.int32),
            cache, st_.page_table, jnp.asarray([0], jnp.int32),
            backend=backend, interpret=interp)

    def full_logits(n):
        h, _, _ = model.forward(params, {"tokens": jnp.asarray(toks[:n][None])})
        return np.asarray(model.logits_fn(params, h[:, -1:]))[0, 0]

    # prefill in two uneven chunks, then single-token decode steps
    logits, cache = paged(toks[:6], 0)
    logits, cache = paged(toks[6:n_prompt], 6)
    np.testing.assert_allclose(np.asarray(logits)[0, 0],
                               full_logits(n_prompt), atol=1e-4, rtol=1e-4)
    for i in range(n_decode):
        pos = n_prompt + i
        logits, cache = paged(toks[pos:pos + 1], pos)
        np.testing.assert_allclose(np.asarray(logits)[0, 0],
                                   full_logits(pos + 1),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# speculative decode certification
# ---------------------------------------------------------------------------


def _periodic_prompt(rng, vocab, period, reps):
    motif = rng.integers(0, vocab, period).astype(np.int32)
    return np.tile(motif, reps)


def _check_spec_vs_baseline(model, params, prompts, steps, spec_k=4,
                            **ecfg_kw):
    """Certify: greedy speculative decode is token-identical to the
    non-speculative engine (the PR-3 baseline path) on the same
    requests. Returns the speculative engine for stats assertions."""
    base = ServingEngine(model, params, EngineConfig(**ecfg_kw))
    ref = base.run(list(prompts), steps)
    eng = ServingEngine(model, params,
                        EngineConfig(spec_k=spec_k, **ecfg_kw))
    out = eng.run(list(prompts), steps)
    eng.sched.check_invariants()
    for i, (a, b) in enumerate(zip(ref, out)):
        assert a.tolist() == b.tolist(), \
            f"req {i}: spec {b.tolist()} != baseline {a.tolist()}"
    return eng, base


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_spec_decode_token_parity(sparse):
    """Acceptance: speculative greedy decode == plain greedy decode,
    dense and sparse junctions, with drafts actually being accepted
    (repetitive prompts feed the prompt-lookup drafter)."""
    cfg = _tiny_cfg(sparse=sparse)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(21)
    prompts = [_periodic_prompt(rng, cfg.vocab_size, 5, 3),
               rng.integers(0, cfg.vocab_size, 9).astype(np.int32)]
    eng, base = _check_spec_vs_baseline(
        model, params, prompts, 16,
        max_slots=2, page_size=4, total_pages=24, max_pages_per_seq=10,
        token_budget=24, prefill_chunk=8, backend="xla")
    assert eng.spec_k == 4
    assert eng.sched.stats["spec_drafted"] > 0
    # the multi-token verify must compress steps whenever drafts land
    if eng.sched.stats["spec_accepted"] > 0:
        assert eng.sched.stats["steps"] < base.sched.stats["steps"]


def test_spec_decode_parity_sliding_window_reclamation():
    """Speculation + window reclamation together: rollback must never
    collide with prefix release (reclaim runs only after truncate)."""
    cfg = _tiny_cfg(sparse=False, layer_pattern=("local",), attn_window=6)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(22)
    prompts = [_periodic_prompt(rng, cfg.vocab_size, 4, 3),
               rng.integers(0, cfg.vocab_size, 5).astype(np.int32)]
    eng, _ = _check_spec_vs_baseline(
        model, params, prompts, 24,
        max_slots=2, page_size=4, total_pages=16, max_pages_per_seq=16,
        token_budget=16, prefill_chunk=8, backend="xla")
    assert eng.sched.window == 6
    assert eng.sched.stats["reclaimed_pages"] > 0
    assert eng.sched.stats["spec_drafted"] > 0


def test_spec_decode_parity_under_preemption():
    """A pool too small for all requests forces evict + recompute while
    speculation is active; outputs still match the baseline engine."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(23)
    prompts = [_periodic_prompt(rng, cfg.vocab_size, 4, 2 + i % 2)
               for i in range(4)]
    eng, _ = _check_spec_vs_baseline(
        model, params, prompts, 8,
        max_slots=4, page_size=4, total_pages=7, max_pages_per_seq=6,
        token_budget=12, prefill_chunk=8, backend="xla")
    assert eng.sched.stats["preempted"] > 0, \
        "pool was sized to force preemption"


def test_spec_decode_parity_hybrid_attention_arch():
    """gemma3 smoke (sliding-window locals + globals under scan groups):
    an attention-only hybrid serves speculatively with full parity."""
    from repro.configs import get_config
    cfg = get_config("gemma3_4b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(24)
    prompts = [_periodic_prompt(rng, cfg.vocab_size, 4, 2),
               rng.integers(0, cfg.vocab_size, 6).astype(np.int32)]
    eng, _ = _check_spec_vs_baseline(
        model, params, prompts, 6,
        max_slots=2, page_size=4, total_pages=12, max_pages_per_seq=6,
        token_budget=16, prefill_chunk=8, backend="xla")
    assert eng.spec_k == 4


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2_1p2b"])
def test_spec_clamped_for_recurrent_stacks(arch):
    """Mamba / hybrid-mamba stacks cannot roll a recurrence back, so the
    engine must clamp ``spec_k`` to 0 — and still serve with parity."""
    from repro.configs import get_config
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(25)
    prompts = [_periodic_prompt(rng, cfg.vocab_size, 4, 2),
               rng.integers(0, cfg.vocab_size, 7).astype(np.int32)]
    eng, _ = _check_spec_vs_baseline(
        model, params, prompts, 6,
        max_slots=2, page_size=4, total_pages=12, max_pages_per_seq=6,
        token_budget=16, prefill_chunk=8, backend="xla")
    assert eng.spec_k == 0
    assert eng.sched.stats["spec_drafted"] == 0


# ---------------------------------------------------------------------------
# engine bugfix regressions
# ---------------------------------------------------------------------------


def test_add_request_rejects_duplicate_req_id():
    """Regression: an explicit req_id duplicating a queued or in-flight
    request silently cross-wired outputs/ttft between the two."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, page_size=4, total_pages=12, max_pages_per_seq=6,
        token_budget=16, prefill_chunk=8, backend="xla"))
    p = np.arange(4, dtype=np.int32)
    eng.add_request(p, 2, req_id=5)
    with pytest.raises(ValueError, match="req_id 5"):
        eng.add_request(p, 2, req_id=5)          # duplicate while queued
    eng.step()                                   # admit into a slot
    with pytest.raises(ValueError, match="req_id 5"):
        eng.add_request(p, 2, req_id=5)          # duplicate in flight
    while eng.sched.has_work():
        eng.step()
    assert len(eng.outputs[5]) == 2
    eng.add_request(p, 1, req_id=5)              # finished id: reusable
    # auto ids keep advancing past explicit ones
    assert eng.add_request(p, 1) > 5


def test_run_tolerates_preempt_only_plan(monkeypatch):
    """Regression: a plan with zero tokens and zero admissions but a
    preemption (allocations failed AFTER preemption freed pages) made
    ``run`` declare the engine stuck, even though the freed pages let
    the very next step progress."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, page_size=4, total_pages=12, max_pages_per_seq=6,
        token_budget=16, prefill_chunk=8, backend="xla"))
    real = eng.sched.schedule
    first = {"done": False}

    def preempt_only_once():
        if not first["done"]:
            first["done"] = True
            return StepPlan(decode_slots=[], prefills=[], preempted=[0])
        return real()

    monkeypatch.setattr(eng.sched, "schedule", preempt_only_once)
    outs = eng.run([np.arange(4, dtype=np.int32)], 3)   # pre-fix: raises
    assert len(outs[0]) == 3


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------


def test_engine_greedy_token_parity_32_steps():
    """Acceptance: paged-cache decode is token-identical to the
    full-recompute path over >= 32 greedy steps, 4 mixed-length prompts
    through continuous batching (smoke-sized engine, CI tier-1)."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8, 16)]
    eng = _check_engine_parity(
        model, params, prompts, 32,
        EngineConfig(max_slots=4, page_size=8, total_pages=28,
                     max_pages_per_seq=7, token_budget=20,
                     prefill_chunk=8, backend="xla"))
    assert eng.sched.stats["finished"] == 4


def test_engine_sparse_junctions_and_pallas_decode():
    """Sparse FFN junctions + the Pallas paged-decode kernel (interpret)
    through the engine, vs full recompute."""
    cfg = _tiny_cfg(sparse=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]
    _check_engine_parity(
        model, params, prompts, 8,
        EngineConfig(max_slots=2, page_size=4, total_pages=12,
                     max_pages_per_seq=6, token_budget=16,
                     prefill_chunk=8, backend="pallas", interpret=True))


def test_engine_preemption_recompute_parity():
    """A pool too small for all requests forces evict + recompute
    preemption; outputs must still match isolated generation."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 12, 5, 9)]
    eng = _check_engine_parity(
        model, params, prompts, 8,
        EngineConfig(max_slots=4, page_size=4, total_pages=7,
                     max_pages_per_seq=6, token_budget=8,
                     prefill_chunk=8, backend="xla"))
    assert eng.sched.stats["preempted"] > 0, \
        "pool was sized to force preemption"


def test_engine_ssm_state_through_cache_interface():
    """Mamba recurrent state rides the paged-cache interface: per-slot
    state rows advance over exact prompt chunks and survive continuous
    batching."""
    from repro.configs import get_config
    cfg = get_config("mamba2-130m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 12)]
    _check_engine_parity(
        model, params, prompts, 6,
        EngineConfig(max_slots=2, page_size=4, total_pages=12,
                     max_pages_per_seq=6, token_budget=16,
                     prefill_chunk=8, backend="xla"))


def test_engine_slot_reuse_resets_ssm_state():
    """Regression: a freed slot re-admitted for a new request must not
    leak the previous occupant's recurrent state. One slot serves two
    mamba requests back-to-back; the second must match isolated
    generation (stale ssd/conv state would corrupt it)."""
    from repro.configs import get_config
    cfg = get_config("mamba2-130m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 7)]
    _check_engine_parity(
        model, params, prompts, 6,
        EngineConfig(max_slots=1, page_size=4, total_pages=6,
                     max_pages_per_seq=4, token_budget=16,
                     prefill_chunk=8, backend="xla"))


@pytest.mark.parametrize("arch", ["gemma3_4b", "zamba2_1p2b",
                                  "deepseek_moe_16b"])
def test_engine_parity_structured_archs(arch):
    """Engine vs full recompute on the structurally-interesting stacks:
    gemma3 (5:1 sliding-window local layers + scan groups), zamba2
    (mamba backbone + shared attention block with its own page pools
    under scan), deepseek-moe (routed experts; capacity unconstrained so
    decode and teacher-forcing see the same expert assignment)."""
    from repro.configs import get_config
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:
        import dataclasses
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                capacity_factor=100.0))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9)]
    _check_engine_parity(
        model, params, prompts, 6,
        EngineConfig(max_slots=2, page_size=4, total_pages=12,
                     max_pages_per_seq=6, token_budget=16,
                     prefill_chunk=8, backend="xla"))


def test_engine_rejects_capacity_constrained_moe():
    """Finite expert capacity + garbage rows from inactive slots would
    let empty slots evict real tokens from expert buckets; the engine
    must refuse and point at dropless decode (the legacy loop and the
    generate() wrapper handle the fallback)."""
    from repro.nn import MoEConfig
    cfg = _tiny_cfg(sparse=False).with_(
        moe=MoEConfig(n_routed=4, top_k=1, d_expert=64,
                      capacity_factor=1.25))
    model = build_model(cfg)
    with pytest.raises(NotImplementedError, match="capacity"):
        ServingEngine(model, None, EngineConfig(
            max_slots=2, page_size=4, total_pages=8, max_pages_per_seq=4))


def test_generate_wrapper_routes_through_engine():
    """launch.serve.generate == the legacy dense-cache loop (greedy), now
    served by the engine underneath."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 8)), jnp.int32)
    toks_eng, _ = generate(model, params, prompt, s_max=24, steps=6)
    toks_ref, _ = generate_cached(model, params, prompt, s_max=24, steps=6)
    np.testing.assert_array_equal(np.asarray(toks_eng),
                                  np.asarray(toks_ref))


def test_generate_cached_nongreedy_splits_key_per_step():
    """The sampled path draws the FIRST token too (not argmax) and uses a
    fresh split every step: different keys give different streams, and no
    two steps of one stream reuse the same draw pattern degenerately."""
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(12)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    outs = []
    for seed in (1, 2, 3):
        toks, _ = generate_cached(model, params, prompt, s_max=24, steps=6,
                                  greedy=False, key=jax.random.key(seed))
        outs.append(np.asarray(toks))
    greedy, _ = generate_cached(model, params, prompt, s_max=24, steps=6)
    # all three sampled streams equal to greedy would mean sampling is off
    assert any((o != np.asarray(greedy)).any() for o in outs)
    # first token is sampled: with 3 keys over vocab 256, at least one
    # first-token draw should differ from the greedy argmax
    assert any((o[:, 0] != np.asarray(greedy)[:, 0]).any() for o in outs)
    # determinism: same key -> same stream
    again, _ = generate_cached(model, params, prompt, s_max=24, steps=6,
                               greedy=False, key=jax.random.key(1))
    np.testing.assert_array_equal(outs[0], np.asarray(again))


def test_serving_smoke_mixed_requests():
    """CI smoke: tiny config, 4 mixed-length requests, 8 decode steps —
    the fast end-to-end gate for the serving workflow."""
    cfg = _tiny_cfg(sparse=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 6, 12)]
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, page_size=4, total_pages=24, max_pages_per_seq=6,
        token_budget=16, prefill_chunk=8, backend="xla"))
    outs = eng.run(prompts, 8)
    assert all(len(o) == 8 for o in outs)
    eng.sched.check_invariants()
    assert eng.sched.stats["finished"] == 4
    assert eng.sched.state.free() == 24  # all pages returned


# ---------------------------------------------------------------------------
# observability: engine counters + bounded host state (the PR-7 ttft leak)
# ---------------------------------------------------------------------------


def test_engine_obs_counters_consistent():
    """Engine metrics agree with the run's ground truth: emitted tokens ==
    sum of output lengths, request lifecycle balances, spec proposed ==
    accepted + rolled_back, TTFT histogram has one sample per request,
    and page occupancy stays a fraction."""
    from repro.obs.metrics import Registry
    cfg = _tiny_cfg(sparse=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(31)
    reg = Registry()
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, page_size=4, total_pages=24, max_pages_per_seq=8,
        token_budget=16, prefill_chunk=8, backend="xla", spec_k=4),
        registry=reg)
    prompts = [np.full(6 + i, (11 * i + 3) % cfg.vocab_size, np.int32)
               for i in range(4)]
    outs = eng.run(prompts, 10)

    emitted = reg.counter("serving_emitted_tokens_total").value()
    assert emitted == sum(len(o) for o in outs) == 40
    req = reg.counter("serving_requests_total")
    assert req.value(event="added") == 4
    assert req.value(event="finished") == 4
    cnt, _ = reg.histogram("serving_ttft_seconds").stats()
    assert cnt == 4                       # exactly one TTFT per request
    icnt, _ = reg.histogram("serving_itl_seconds").stats()
    assert icnt > 0
    spec = reg.counter("serving_spec_tokens_total")
    drafted = spec.value(result="proposed")
    assert drafted > 0
    assert drafted == spec.value(result="accepted") \
        + spec.value(result="rolled_back")
    # the engine's phase counter and the scheduler's plan counter count
    # the same drafts independently
    assert reg.counter("serving_tokens_total").value(
        phase="spec_draft") == drafted
    assert reg.counter("sched_plan_tokens_total").value(
        phase="draft") == drafted
    assert drafted == eng.sched.stats["spec_drafted"]
    assert 0.0 <= reg.gauge("serving_page_occupancy").value() <= 1.0
    assert reg.gauge("serving_pages_highwater").value() > 0
    scnt, ssum = reg.histogram("repro_span_seconds").stats(
        span="engine/step")
    assert scnt == eng.sched.stats["steps"] and ssum > 0


def test_engine_host_state_bounded_over_many_requests():
    """Regression for the PR-7 leak: per-request host dicts must not grow
    with completed requests. Run several waves through one engine and
    assert the timestamp map drains and registry cardinality is flat."""
    from repro.obs.metrics import Registry
    cfg = _tiny_cfg(sparse=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(33)
    reg = Registry()
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, page_size=4, total_pages=16, max_pages_per_seq=4,
        token_budget=12, prefill_chunk=8, backend="xla"), registry=reg)
    series_after_wave = []
    for wave in range(3):
        prompts = [rng.integers(0, cfg.vocab_size, 3 + (i + wave) % 4
                                ).astype(np.int32) for i in range(6)]
        eng.run(prompts, 4)
        assert eng._t_added == {}, "admission timestamps must drain"
        assert all(t is None for t in eng._last_tok)
        h = reg.histogram("serving_ttft_seconds")
        series_after_wave.append(
            (len(h.series),
             len(reg.counter("serving_requests_total").series)))
    # 18 requests later: per-metric series counts did not grow past wave 1
    assert series_after_wave[0] == series_after_wave[-1]
    cnt, _ = reg.histogram("serving_ttft_seconds").stats()
    assert cnt == 18
    # outputs were popped by run(); nothing references finished requests
    assert eng.outputs == {}
