"""Continuous-batching serving engine (deepspeed_tpu/serving/).

The two load-bearing acceptance properties:

- **Parity**: greedy tokens produced for each request under continuous
  batching — staggered arrivals, mixed lengths, eviction and
  chaos-driven cancellation churn — are BIT-IDENTICAL to
  single-sequence ``generate()`` (the paged pool gathers a wider padded
  key view, but exact -1e30 masking makes the attention math equal).
- **Recompile guard**: after ``warmup()``, requests joining / leaving /
  completing across >= 20 decode steps trigger ZERO new XLA
  compilations (CompilationCounter hook) — the decode program is ONE
  fixed-shape jit with slot masking.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.runtime.resilience.watchdog import TrainingWatchdog
from deepspeed_tpu.serving.engine import InferenceEngine
from deepspeed_tpu.serving.kv_cache import PagedKVPool, pool_shapes
from deepspeed_tpu.serving.metrics import CompilationCounter, ServingMetrics
from deepspeed_tpu.serving.scheduler import (Request, RequestState,
                                              Scheduler)


@pytest.fixture(scope="module")
def toy():
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, dtype=jnp.float32, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    refs = {}

    def ref(prompt, max_new):
        key = (tuple(int(t) for t in prompt), max_new)
        if key not in refs:
            refs[key] = generate(model, params,
                                 np.asarray(prompt, np.int32)[None],
                                 max_new_tokens=max_new)[0]
        return refs[key]

    return model, params, ref


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_blocks_per_seq", 8)
    return InferenceEngine(model, params, **kw)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# parity (acceptance)
# ---------------------------------------------------------------------------

def test_parity_staggered_mixed_lengths(toy):
    """Greedy continuous batching == single-sequence generate(), with
    arrivals staggered across steps and mixed prompt/output lengths."""
    model, params, ref = toy
    eng = _engine(model, params)
    prompts = _prompts(1, (5, 11, 3, 9))
    maxnew = [6, 9, 12, 5]
    rids = []
    for p, m in zip(prompts, maxnew):
        rids.append(eng.submit(p, max_new_tokens=m))
        eng.step()                       # stagger arrivals
        eng.step()
    res = eng.serve(max_steps=500)
    for rid, p, m in zip(rids, prompts, maxnew):
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))
    rep = eng.serving_report()
    assert rep["requests"]["completed"] == 4
    assert rep["ttft_s"]["mean"] is not None
    assert rep["throughput"]["tokens_per_slot_step"] > 0


def test_parity_under_eviction_churn(toy):
    """A pool too small for both sequences forces preemption; the evicted
    request re-prefills prompt+generated and must still match
    single-sequence generate() bit for bit."""
    model, params, ref = toy
    eng = _engine(model, params, max_slots=2, kv_blocks=9)
    prompts = _prompts(2, (9, 10))
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    res = eng.serve(max_steps=500)
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 12))
    assert eng.serving_report()["requests"]["evictions"] >= 1, \
        "pool sizing failed to exercise eviction"


def test_parity_under_chaos_cancellation(toy):
    """chaos.arm(cancel_request_every=N) drives request cancellation
    through the scheduler; surviving requests stay bit-identical and the
    cancelled ones report partial tokens."""
    model, params, ref = toy
    eng = _engine(model, params)
    prompts = _prompts(3, (5, 11, 3, 9, 6))
    maxnew = [6, 9, 12, 5, 8]
    chaos.arm(cancel_request_every=7)
    try:
        rids = []
        for p, m in zip(prompts, maxnew):
            rids.append(eng.submit(p, max_new_tokens=m))
            eng.step()
            eng.step()
        res = eng.serve(max_steps=500)
    finally:
        plan = chaos.active()
        chaos.disarm()
    assert any(kind == "cancel_request" for kind, _ in plan.fired)
    finished = cancelled = 0
    for rid, p, m in zip(rids, prompts, maxnew):
        r = res[rid]
        if r["status"] == "cancelled":
            cancelled += 1
            # partial output is a prefix of the reference continuation
            np.testing.assert_array_equal(
                r["tokens"], ref(p, m)[:len(r["tokens"])])
        else:
            finished += 1
            np.testing.assert_array_equal(r["tokens"], ref(p, m))
    assert cancelled >= 1 and finished >= 1
    assert eng.serving_report()["requests"]["cancelled"] == cancelled


def test_parity_eos_early_stop(toy):
    """A request that hits eos stops early and matches the eos-latched
    generate() output up to (and including) the first eos."""
    model, params, ref = toy
    prompt = _prompts(4, (6,))[0]
    base = ref(prompt, 10)
    eos = int(base[len(prompt) + 2])     # appears mid-continuation
    eng = _engine(model, params)
    rid = eng.submit(prompt, max_new_tokens=10, eos_token_id=eos)
    res = eng.serve(max_steps=200)
    got = res[rid]["tokens"]
    gen = generate(model, params, prompt[None], max_new_tokens=10,
                   eos_token_id=eos)[0]
    stop = len(prompt) + list(gen[len(prompt):]).index(eos) + 1
    np.testing.assert_array_equal(got, gen[:stop])
    assert got[-1] == eos


# ---------------------------------------------------------------------------
# recompile guard (acceptance)
# ---------------------------------------------------------------------------

def test_zero_recompiles_after_warmup(toy):
    """>= 20 decode steps of join/leave/complete churn compile NOTHING
    new after warmup.  (The decode program's host-transfer-free /
    pool-donation HLO contracts are declared on decode_step in the
    program registry and checked by the --programs autopilot,
    tests/unit/test_program_lint.py.)"""
    model, params, ref = toy
    eng = _engine(model, params)
    eng.warmup()
    prompts = _prompts(5, (5, 11, 3, 9, 6, 4, 7))
    maxnew = [6, 9, 12, 5, 8, 7, 10]
    with CompilationCounter() as cc:
        rids = []
        for p, m in zip(prompts, maxnew):
            rids.append(eng.submit(p, max_new_tokens=m))
            eng.step()
            eng.step()
        eng.serve(max_steps=500)
    assert eng.metrics.decode_steps >= 20, eng.metrics.decode_steps
    assert cc.count == 0, \
        f"{cc.count} XLA compilations during steady-state churn"
    for rid, p, m in zip(rids, prompts, maxnew):
        np.testing.assert_array_equal(eng.results[rid]["tokens"],
                                      ref(p, m))


def test_warmup_covers_multichunk_prompts_on_small_capacity(toy):
    """Regression (review round 1): capacity too small for
    chunk+bucket+2 warmup prompts must still compile the NON-final
    prefill variant — a post-warmup prompt longer than prefill_chunk
    used to pay a steady-state compile."""
    model, params, ref = toy
    eng = InferenceEngine(model, params, max_slots=2, kv_block_size=4,
                          prefill_chunk=16, max_blocks_per_seq=5)
    assert eng.capacity_per_seq == 20    # chunk+4+2 > 20 for every bucket
    eng.warmup()
    prompt = _prompts(14, (17,))[0]      # needs a non-final chunk
    with CompilationCounter() as cc:
        rid = eng.submit(prompt, max_new_tokens=3)
        eng.serve(max_steps=100)
    assert cc.count == 0, \
        f"{cc.count} compiles for an admissible post-warmup prompt"
    np.testing.assert_array_equal(eng.results[rid]["tokens"],
                                  ref(prompt, 3))


def test_steady_state_pool_is_updated_in_place(toy):
    """Donation proof at the array level: after a decode step the
    PREVIOUS pool buffers are deleted (consumed in place), not copied."""
    model, params, _ = toy
    eng = _engine(model, params)
    eng.submit(_prompts(6, (5,))[0], max_new_tokens=4)
    eng.step()                            # prefill
    before = eng.pool.tensors.arrays
    eng.step()                            # decode consumes the pool
    assert all(t.is_deleted() for t in before)


# ---------------------------------------------------------------------------
# sharded decode
# ---------------------------------------------------------------------------

def test_sharded_decode_parity_and_zero_collectives(toy, eight_devices):
    """Batch-axis sharding over a 2-device mesh: identical greedy tokens,
    and the compiled decode program moves ZERO collective bytes (the
    placement-semantics claim priced in comm_budgets.json)."""
    from jax.sharding import Mesh
    from tools.graftlint import hlo_contracts as hc

    model, params, ref = toy
    mesh = Mesh(np.array(eight_devices[:2]), ("data",))
    eng = _engine(model, params, max_slots=4, shards=2, mesh=mesh)
    prompts = _prompts(7, (5, 11, 7, 4))
    rids = [eng.submit(p, max_new_tokens=7) for p in prompts]
    res = eng.serve(max_steps=500)
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 7))
    hlo = eng.decode_hlo()
    assert hc.collective_bytes(hlo) == 0, [
        c.line for c in hc.collective_ops(hlo)]
    hc.assert_no_host_transfers(hlo, "sharded serving decode")


def test_sharded_pool_kv_handoff_bit_identical(toy, eight_devices):
    """Per-shard KV handoff (ISSUE 16 lifts the PR-11 shards=1 limit):
    a SHARDED source pool exports GLOBAL block rows and a sharded
    destination adopts them into whichever shard its free slot pins —
    decode resumes bit-identically with no re-prefill, including for a
    request whose blocks live on a non-zero source shard (the case the
    old local-id gather would have silently mis-addressed)."""
    from jax.sharding import Mesh

    model, params, ref = toy
    mesh = Mesh(np.array(eight_devices[:2]), ("data",))
    eng_a = _engine(model, params, max_slots=4, shards=2, mesh=mesh)
    eng_b = _engine(model, params, max_slots=4, shards=2, mesh=mesh)
    prompts = _prompts(21, (5, 9, 7, 6))
    maxnew = [8, 6, 7, 9]
    rids = [eng_a.submit(p, max_new_tokens=m, _rid=100 + i)
            for i, (p, m) in enumerate(zip(prompts, maxnew))]
    for _ in range(3):
        eng_a.step()
    by_shard = {eng_a.scheduler.requests[r].shard for r in rids
                if eng_a.scheduler.requests[r].state.value == "running"}
    assert by_shard == {0, 1}, "fixture must populate both source shards"
    moved = {}
    for rid, p, m in zip(list(rids), prompts, maxnew):
        req = eng_a.scheduler.requests.get(rid)
        if req is None or req.state.value != "running":
            continue
        entry = eng_a.export_request(rid)
        assert eng_b.import_request(entry) == "adopted"
        moved[rid] = (p, m)
    assert len(moved) >= 2
    res_b = eng_b.serve(max_steps=500)
    for rid, (p, m) in moved.items():
        assert res_b[rid]["status"] == "finished"
        np.testing.assert_array_equal(res_b[rid]["tokens"], ref(p, m))


def test_decode_collectives_accounting():
    from deepspeed_tpu.runtime import comm_accounting as ca

    assert ca.serving_decode_collectives(24, 1024, 50304, 8, tp=1) == []
    tp = ca.serving_decode_collectives(24, 1024, 50304, 8, tp=8,
                                       act_dtype="bfloat16")
    assert len(tp) == 24 * 2 + 1
    assert all(c.op == "all-reduce" for c in tp)
    # 2(w-1)/w * n * s per activation all-reduce
    act = [c for c in tp if c.name.startswith("decode_ar:attn_out")][0]
    assert act.bytes_per_device == int(2 * (7 / 8) * 8 * 1024 * 2)


# ---------------------------------------------------------------------------
# int8 KV
# ---------------------------------------------------------------------------

def test_int8_kv_arms_and_serves(toy):
    model, params, _ = toy
    eng = _engine(model, params, quantize_kv=True)
    assert eng.pool.quantized
    assert eng.n_pool_tensors() == 4
    prompt = _prompts(8, (6,))[0]
    rid = eng.submit(prompt, max_new_tokens=8)
    res = eng.serve(max_steps=200)
    toks = res[rid]["tokens"]
    assert toks.shape == (14,) and toks.max() < 97
    np.testing.assert_array_equal(toks[:6], prompt)


def test_int8_kv_disarms_when_unprofitable(caplog):
    """bf16 pool with head_dim <= 4: the f32 scale costs more than int8
    saves — must warn DISARMED and serve full precision."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    cfg = GPT2Config(vocab_size=32, n_positions=32, n_embd=8, n_layer=1,
                     n_head=2, dtype=jnp.bfloat16, loss_chunk_tokens=0)
    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            pool = PagedKVPool(cfg, num_blocks=4, block_size=4,
                               quantize_kv=True)
    finally:
        ds_logger.propagate = False
    assert not pool.quantized
    assert any("DISARMED" in r.message for r in caplog.records)
    assert pool.tensors.k.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the pool's layout (kv_cache.pool_shapes: index dims major, token row minor)
# ---------------------------------------------------------------------------

_LAYOUT_CFG = GPT2Config(vocab_size=32, n_positions=32, n_embd=32, n_layer=2,
                         n_head=4, dtype=jnp.bfloat16, loss_chunk_tokens=0)


def _layout_round_trip(quantized):
    """``_pool_write`` then ``_pool_view`` against a plain dictionary
    {(layer, block, offset): the token's (H, D) row}."""
    from deepspeed_tpu.serving.engine import _pool_view, _pool_write

    cfg = _LAYOUT_CFG
    L, H, D, NB, bs = cfg.n_layer, cfg.n_head, cfg.head_dim, 7, 4
    pool = PagedKVPool(cfg, num_blocks=NB, block_size=bs,
                       quantize_kv=quantized)
    assert pool.quantized == quantized
    assert tuple(t.shape for t in pool.tensors.arrays) == tuple(
        sh for sh in pool_shapes(cfg, NB, bs, quantized) if sh is not None)
    rng = np.random.default_rng(5)
    k, scales = pool.tensors.k, pool.tensors.k_scale
    written = {}
    for l in range(L):
        # five tokens a layer, scattered over blocks and offsets
        cells = rng.permutation(NB * bs)[:5]
        blk, off = cells // bs, cells % bs
        rows = rng.normal(size=(5, H, D)).astype(np.float32)
        k, scales = _pool_write(k, scales, l, jnp.asarray(blk),
                                jnp.asarray(off), jnp.asarray(rows),
                                quantized)
        for b, o, row in zip(blk, off, rows):
            written[l, int(b), int(o)] = row
    tables = jnp.asarray(rng.permutation(NB)[:6].reshape(2, 3))   # (B, W)
    for l in range(L):
        view = np.asarray(_pool_view(k, scales, l, tables, H, quantized,
                                     jnp.float32), np.float32)
        assert view.shape == (2, H, 3 * bs, D)
        for b, w, o in np.ndindex(2, 3, bs):
            got = view[b, :, w * bs + o, :]                       # (H, D)
            row = written.get((l, int(tables[b, w]), o))
            if row is None:
                assert not got.any()
            elif quantized:
                # one symmetric scale per (token, head), as stored
                scale = np.abs(row).max(axis=-1, keepdims=True) / 127.0
                np.testing.assert_array_equal(
                    np.asarray(scales[l, int(tables[b, w]), o]),
                    scale[:, 0])
                np.testing.assert_allclose(
                    got, np.round(row / scale) * scale, rtol=1e-6)
            else:
                np.testing.assert_array_equal(
                    got, np.asarray(jnp.asarray(row, jnp.bfloat16),
                                    np.float32))


def _layout_handoff(toy):
    """A payload exported from one engine holds each pool tensor's W
    gathered blocks in the pool's own layout, and the engine that imports
    it goes on to the same tokens."""
    model, params, ref = toy
    eng_a, eng_b = _engine(model, params), _engine(model, params)
    prompt = _prompts(31, (9,))[0]
    rid = eng_a.submit(prompt, max_new_tokens=10)
    for _ in range(4):
        eng_a.step()
    entry = eng_a.export_request(rid)
    assert tuple(part.shape for part in entry["kv"]) == pool_shapes(
        model.config, eng_a.W, eng_a.bs, False)[:2]
    assert eng_b.import_request(entry) == "adopted"
    res = eng_b.serve(max_steps=200)
    np.testing.assert_array_equal(res[rid]["tokens"], ref(prompt, 10))


def _layout_cow_split():
    """The COW split copies one block of every pool tensor, scales
    included, and only that block."""
    pool = PagedKVPool(_LAYOUT_CFG, num_blocks=6, block_size=4,
                       quantize_kv=True)
    rng = np.random.default_rng(6)
    before = [rng.integers(-100, 100, t.shape).astype(t.dtype)
              for t in pool.tensors.arrays]
    pool.tensors = type(pool.tensors)(*(jnp.asarray(b) for b in before))
    pool._cow_copy(0, 2, 4)
    for was, now in zip(before, pool.tensors.arrays):
        want = was.copy()
        want[:, 4] = was[:, 2]
        np.testing.assert_array_equal(np.asarray(now), want)


LAYOUT_CASES = {
    "round-trip-bf16": lambda toy: _layout_round_trip(quantized=False),
    "round-trip-int8": lambda toy: _layout_round_trip(quantized=True),
    "handoff": _layout_handoff,
    "cow-split": lambda toy: _layout_cow_split(),
}


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_pool_layout(case, toy):
    LAYOUT_CASES[case](toy)


# ---------------------------------------------------------------------------
# scheduler / allocator units (no model)
# ---------------------------------------------------------------------------

def _req(rid, n=4, prio=0, max_new=4):
    return Request(rid=rid, prompt=np.zeros(n, np.int32),
                   max_new_tokens=max_new, priority=prio)


def test_scheduler_priority_then_fcfs():
    s = Scheduler(2)
    for rid, prio in [(0, 1), (1, 0), (2, 1), (3, 0)]:
        s.submit(_req(rid, prio=prio))
    order = []
    while True:
        r = s.start_admission()
        if r is None:
            break
        order.append(r.rid)
        s.promote(r)
    # both slots fill in priority order; FCFS within a class
    assert order == [1, 3]
    assert s.peek_waiting().rid == 0


def test_scheduler_victim_policy():
    s = Scheduler(3)
    for rid, prio in [(0, 0), (1, 1), (2, 1)]:
        s.submit(_req(rid, prio=prio))
        r = s.start_admission()
        s.promote(r)
    newcomer = _req(9, prio=0)
    # admission: only strictly-less-important victims; youngest first
    v = s.victim(for_req=newcomer, admission=True)
    assert v.rid == 2
    # growth of a prio-1 runner may preempt its own class but not rid 0
    v = s.victim(for_req=s.running[1], admission=False)
    assert v.rid == 2
    # a prio-0 grower with only itself and less-important peers
    v = s.victim(for_req=s.running[0], admission=False)
    assert v.rid == 2
    # shard filter
    assert s.victim(for_req=newcomer, admission=True, shard=3) is None


def test_scheduler_dropped_prefill_is_the_next_admission_again():
    """A prefill the engine drops (pool pressure) goes back in line at
    its own FCFS age with its progress reset, and the slot it held is
    free for the request behind it."""
    s = Scheduler(2)
    for rid in range(3):
        s.submit(_req(rid))
    a = s.start_admission()
    a.prefill_done = 4                    # a chunk had been written
    s.drop_prefill(a, requeue=True)       # engine couldn't fit it
    assert s.prefilling is None and s.queue_depth() == 3
    assert (a.state, a.slot, a.prefill_done) == (RequestState.WAITING,
                                                 None, 0)
    a2 = s.start_admission()
    assert a2 is a and a2.slot == 0       # FCFS: same request retries
    s.promote(a2)
    b = s.start_admission()
    assert b.rid == 1 and b.slot == 1     # the slot behind it is free
    s.promote(b)
    assert s.start_admission() is None    # both slots running
    # without requeue the request leaves the line for good
    s.finish(a2)
    c = s.start_admission()
    s.drop_prefill(c, requeue=False)
    assert c.rid == 2 and s.prefilling is None
    assert s.start_admission() is None and s.queue_depth() == 0


def test_admission_spreads_across_shard_pools(toy, eight_devices):
    """Slot placement follows pool pressure: with 2 shards, the first
    two admissions land on DIFFERENT shards (most-free-blocks ranking),
    not both on shard 0."""
    from jax.sharding import Mesh

    model, params, _ = toy
    mesh = Mesh(np.array(eight_devices[:2]), ("data",))
    eng = _engine(model, params, max_slots=4, shards=2, mesh=mesh)
    r0 = eng.submit(_prompts(12, (5,))[0], max_new_tokens=16)
    eng.step()                            # admit+prefill r0
    r1 = eng.submit(_prompts(13, (5,))[0], max_new_tokens=16)
    eng.step()                            # admit+prefill r1
    shards = {rid: eng.pool._shard_of[rid] for rid in (r0, r1)}
    assert shards[r0] != shards[r1], shards
    eng.serve(max_steps=200)


def test_pool_allocator_occupancy_and_fragmentation():
    cfg = GPT2Config(vocab_size=32, n_positions=64, n_embd=8, n_layer=1,
                     n_head=2, dtype=jnp.float32, loss_chunk_tokens=0)
    pool = PagedKVPool(cfg, num_blocks=8, block_size=4)
    assert pool.usable_blocks == 7
    assert pool.alloc(0, 0, 6)           # 2 blocks, 6 positions
    assert pool.blocks_in_use == 2
    assert pool.fragmentation() == pytest.approx(1 - 6 / 8)
    assert pool.alloc(1, 0, 20)          # 5 blocks -> pool full
    assert not pool.alloc(2, 0, 5), "overcommit must fail cleanly"
    assert pool.blocks_in_use == 7 and pool.occupancy() == 1.0
    row = pool.table_row(1, 8)
    assert (row[:5] > 0).all() and (row[5:] == 0).all()
    pool.free(0)
    assert pool.alloc(2, 0, 5)
    pool.free(1)
    pool.free(2)
    assert pool.blocks_in_use == 0 and pool.fragmentation() == 0.0


def test_global_table_row_offsets_by_owning_shard():
    """The KV-handoff export/import path addresses the UNSPLIT block
    axis: global ids = local + shard * blocks_per_shard, with padding
    mapped to the owning shard's OWN trash block (never shard 0's)."""
    cfg = GPT2Config(vocab_size=32, n_positions=64, n_embd=8, n_layer=1,
                     n_head=2, dtype=jnp.float32, loss_chunk_tokens=0)
    pool = PagedKVPool(cfg, num_blocks=8, block_size=4, shards=2)
    assert pool.blocks_per_shard == 4
    assert pool.alloc(7, 1, 8)            # 2 blocks pinned to shard 1
    local = pool.table_row(7, 4)
    glob = pool.global_table_row(7, 4)
    assert (local[:2] >= 1).all() and (local[:2] < 4).all()
    np.testing.assert_array_equal(glob, local + 4)
    assert (glob[2:] == 4).all()          # shard 1's trash block
    assert pool.alloc(3, 0, 4)            # shard 0: global == local
    np.testing.assert_array_equal(pool.global_table_row(3, 4),
                                  pool.table_row(3, 4))


def test_submit_rejects_oversized_requests(toy):
    model, params, _ = toy
    eng = _engine(model, params)          # capacity 8 blocks x 4 = 32
    with pytest.raises(AssertionError, match="capacity"):
        eng.submit(np.zeros(30, np.int32), max_new_tokens=10)


# ---------------------------------------------------------------------------
# metrics / reporting / watchdog
# ---------------------------------------------------------------------------

def test_metrics_ttft_tpot_with_fake_clock():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    m.record_submit(7)
    t[0] = 1.5
    m.record_token(7)                     # TTFT = 1.5
    t[0] = 2.0
    m.record_token(7)
    t[0] = 2.5
    m.record_token(7)                     # 2 intervals over 1.0s
    m.record_finish(7)
    m.record_step(queue_depth=2, running=1, slots=4, occupancy=0.5,
                  fragmentation=0.25, decoded=True)
    rep = m.report()
    assert rep["ttft_s"]["mean"] == pytest.approx(1.5)
    assert rep["tpot_s"] == pytest.approx(0.5)
    assert rep["requests"]["completed"] == 1
    assert rep["queue_depth"]["max"] == 2
    assert rep["kv_pool"]["occupancy_max"] == pytest.approx(0.5)


def test_serving_report_and_last_metrics(toy):
    model, params, _ = toy
    eng = _engine(model, params)
    rid = eng.submit(_prompts(9, (5,))[0], max_new_tokens=4)
    eng.serve(max_steps=100)
    rep = eng.serving_report()
    assert rep["config"]["max_slots"] == 3
    assert rep["tokens"]["generated"] == 4
    assert 0.0 <= rep["kv_pool"]["occupancy_max"] <= 1.0
    assert rep["kv_pool"]["now"]["blocks_in_use"] == 0   # all freed
    assert eng._last_metrics["step"] == eng.metrics.steps
    assert eng.results[rid]["status"] == "finished"


def test_watchdog_heartbeats_every_step(toy):
    model, params, _ = toy
    beats = []
    wd = TrainingWatchdog(stall_timeout=1e9,
                          clock=lambda: beats.append(1) or 0.0)
    eng = _engine(model, params, watchdog=wd)
    eng.submit(_prompts(10, (4,))[0], max_new_tokens=3)
    eng.serve(max_steps=100)
    wd.heartbeat()
    assert wd.last_progress_time is not None
    assert len(beats) >= eng.metrics.steps


# ---------------------------------------------------------------------------
# prefix cache + speculative decode (ISSUE 17)
# ---------------------------------------------------------------------------

def _shared_prefix_prompts(seed, n, prefix_len=16, tail=(2, 5)):
    """System-prompt traffic in miniature: one shared prefix, short
    random tails."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 97, prefix_len).astype(np.int32)
    return [np.concatenate(
        [prefix, rng.integers(0, 97,
                              int(rng.integers(*tail))).astype(np.int32)])
        for _ in range(n)]


@pytest.mark.parametrize("cache,spec", [(True, None), (False, 3),
                                        (True, 3)])
def test_parity_cache_and_spec_matrix(toy, cache, spec):
    """THE acceptance parity: greedy tokens with the prefix cache and/or
    speculative decoding armed are BIT-IDENTICAL to single-sequence
    generate() under staggered arrivals on shared-prefix traffic (the
    cache-off/spec-off cell is the existing staggered parity test)."""
    model, params, ref = toy
    eng = _engine(model, params, prefix_cache=cache, speculative=spec)
    prompts = _shared_prefix_prompts(21, 5)
    maxnew = [6, 9, 4, 7, 5]
    rids = []
    for p, m in zip(prompts, maxnew):
        rids.append(eng.submit(p, max_new_tokens=m))
        eng.step()                        # stagger arrivals
        eng.step()
    res = eng.serve(max_steps=500)
    for rid, p, m in zip(rids, prompts, maxnew):
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))
    rep = eng.serving_report()
    if cache:
        assert rep["prefix_cache"]["hits"] >= 1
        assert rep["prefix_cache"]["avoided_prefill_tokens"] > 0
    if spec:
        assert rep["speculative"]["verify_steps"] > 0
        assert sum(k * v for k, v in
                   rep["speculative"]["accept_len_hist"].items()) \
            == rep["speculative"]["accepted_tokens"]


def test_prefix_cache_prefill_ratio_guard(toy):
    """Shared-prefix traffic in miniature (tier-1): the radix cache
    computes >= 2x fewer prefill tokens than the cache-off run of the
    SAME traffic."""
    model, params, ref = toy
    prompts = _shared_prefix_prompts(22, 6)
    maxnew = [4, 6, 3, 5, 4, 6]

    def run(cache):
        eng = _engine(model, params, prefix_cache=cache)
        rids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, maxnew)]
        res = eng.serve(max_steps=500)
        for rid, p, m in zip(rids, prompts, maxnew):
            np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))
        return eng.metrics.prefill_computed_tokens

    computed_off, computed_on = run(False), run(True)
    assert computed_off == sum(len(p) for p in prompts)
    assert computed_off >= 2 * computed_on, (computed_off, computed_on)


def test_prefix_cache_parity_under_shared_block_eviction(toy):
    """A pool too small for the working set forces eviction while shared
    blocks are live: refcounted tree blocks survive their owner's
    eviction (the re-prefill re-attaches them), COW splits keep private
    writes off shared storage, and every token stays bit-identical."""
    model, params, ref = toy
    eng = _engine(model, params, max_slots=2, kv_blocks=10,
                  prefix_cache=True)
    # prefix 10 = 2 full shareable blocks + a 2-position COW overlap;
    # cheap admits, then 16-token continuations outgrow the pool
    prompts = _shared_prefix_prompts(23, 4, prefix_len=10, tail=(2, 4))
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    res = eng.serve(max_steps=800)
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(res[rid]["tokens"], ref(p, 16))
    rep = eng.serving_report()
    assert rep["requests"]["evictions"] >= 1, \
        "pool sizing failed to exercise eviction under sharing"
    assert rep["prefix_cache"]["hits"] >= 1
    assert rep["kv_pool"]["now"]["prefix_cow_splits"] >= 1


def test_pool_radix_refcount_cow_and_reclaim():
    """Radix-tree unit semantics: exact-match sharing, COW split of the
    divergent block, refcounts pinning shared blocks across free(), and
    LRU reclaim returning unreferenced leaves to the allocator."""
    cfg = GPT2Config(vocab_size=32, n_positions=64, n_embd=8, n_layer=1,
                     n_head=2, dtype=jnp.float32, loss_chunk_tokens=0)
    pool = PagedKVPool(cfg, num_blocks=10, block_size=4)
    toks0 = tuple(range(12))              # 3 full blocks
    assert pool.alloc(0, 0, 12)
    assert pool.prefix_insert(0, 0, toks0) == 3
    assert pool.cached_blocks() == 3

    # divergence inside block 3: two full matches + a 2-position COW
    toks1 = toks0[:10] + (31, 30)
    full, cow, cow_len = pool.prefix_lookup(0, toks1)
    assert len(full) == 2 and cow is not None and cow_len == 2
    assert pool.prefix_attach(1, 0, toks1) == 10
    assert pool.cow_splits == 1
    assert pool.blocks_of(1) == 3         # 2 shared + 1 private COW
    assert pool.alloc(1, 0, 14)           # extend for the un-cached tail

    # freeing the inserter must NOT recycle tree-owned blocks…
    in_use = pool.blocks_in_use
    pool.free(0)
    assert pool.blocks_in_use == in_use   # all 3 were tree-owned
    # …and rid1 still decodes against the shared storage
    assert pool.table_row(1, 4)[0] != 0
    pool.free(1)                          # derefs shares, recycles COW

    # allocator pressure reclaims unreferenced LRU leaves, never more
    assert pool.cache_reclaims == 0
    assert pool.alloc(2, 0, 36)           # 9 blocks: needs the tree's 3
    assert pool.cache_reclaims == 3
    assert pool.cached_blocks() == 0
    stats = pool.stats()
    assert stats["prefix_cow_splits"] == 1
    assert stats["prefix_cache_reclaims"] == 3


def test_parity_chaos_cancel_mid_draft(toy):
    """chaos cancellation landing between draft and verify: survivors
    stay bit-identical, cancelled requests report a clean prefix of the
    reference continuation (no half-accepted draft garbage)."""
    model, params, ref = toy
    eng = _engine(model, params, prefix_cache=True, speculative=3)
    prompts = _shared_prefix_prompts(24, 5, prefix_len=12)
    maxnew = [6, 9, 12, 5, 8]
    chaos.arm(cancel_request_every=7)
    try:
        rids = []
        for p, m in zip(prompts, maxnew):
            rids.append(eng.submit(p, max_new_tokens=m))
            eng.step()
            eng.step()
        res = eng.serve(max_steps=500)
    finally:
        plan = chaos.active()
        chaos.disarm()
    assert any(kind == "cancel_request" for kind, _ in plan.fired)
    assert eng.metrics.spec_verify_steps > 0
    finished = cancelled = 0
    for rid, p, m in zip(rids, prompts, maxnew):
        r = res[rid]
        if r["status"] == "cancelled":
            cancelled += 1
            np.testing.assert_array_equal(
                r["tokens"], ref(p, m)[:len(r["tokens"])])
        else:
            finished += 1
            np.testing.assert_array_equal(r["tokens"], ref(p, m))
    assert cancelled >= 1 and finished >= 1


def test_spec_acceptance_histogram_rigged_drafter(toy):
    """Histogram correctness on rigged drafters: an oracle drafter
    accepts full k+1 windows (modulo request-budget tails); a constant
    drafter degrades toward 1 token/verify — and BOTH stay
    bit-identical, because acceptance re-verifies every draft."""
    model, params, ref = toy
    prompts = _prompts(25, (5, 7, 4))
    maxnew = [9, 8, 10]

    def run(drafter):
        eng = _engine(model, params, speculative=3)
        if drafter == "oracle":
            def draft(req, k):
                full = ref(req.prompt, req.max_new_tokens)
                done = len(req.full_tokens)
                nxt = [int(t) for t in full[done:done + k]]
                while len(nxt) < k:
                    nxt.append(int(full[-1]))
                return nxt
            eng._draft_tokens = draft
        elif drafter == "constant":
            eng._draft_tokens = lambda req, k: [96] * k
        rids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, maxnew)]
        res = eng.serve(max_steps=500)
        for rid, p, m in zip(rids, prompts, maxnew):
            np.testing.assert_array_equal(res[rid]["tokens"], ref(p, m))
        hist = dict(eng.metrics.spec_accept_hist)
        # each request's FIRST token comes from the final prefill chunk,
        # so verify steps deliver max_new - 1 tokens per request
        assert sum(k * v for k, v in hist.items()) \
            == eng.metrics.spec_accepted_tokens \
            == sum(maxnew) - len(prompts)
        return hist, eng.metrics.tokens_per_verify()

    hist_o, tpv_o = run("oracle")
    hist_c, tpv_c = run("constant")
    assert max(hist_o) == 4, hist_o       # full k+1 windows accepted
    assert tpv_o > 2.0, (hist_o, tpv_o)
    assert hist_c.get(1, 0) > 0
    assert tpv_o > tpv_c, (tpv_o, tpv_c)


def test_zero_recompiles_with_cache_and_spec(toy):
    """The ISSUE 17 recompile pin: join/leave churn with the prefix
    cache AND speculative decoding armed compiles NOTHING after warmup
    (COW splits included), and the draft-verify program honors the
    decode jit's HLO contracts (host-transfer-free, pool donated)."""
    from tools.graftlint import hlo_contracts as hc

    model, params, ref = toy
    eng = _engine(model, params, prefix_cache=True, speculative=3)
    eng.warmup()
    # prefix 14 = 3 full shareable blocks + a 2-position COW overlap,
    # so the guard window provably contains a COW device copy
    prompts = _shared_prefix_prompts(26, 6, prefix_len=14)
    maxnew = [6, 9, 12, 5, 8, 7]
    with CompilationCounter() as cc:
        rids = []
        for p, m in zip(prompts, maxnew):
            rids.append(eng.submit(p, max_new_tokens=m))
            eng.step()
            eng.step()
        eng.serve(max_steps=500)
    assert cc.count == 0, \
        f"{cc.count} XLA compilations during cache+spec churn"
    assert eng.pool.cow_splits >= 1, \
        "churn never exercised a COW split inside the guard window"
    for rid, p, m in zip(rids, prompts, maxnew):
        np.testing.assert_array_equal(eng.results[rid]["tokens"],
                                      ref(p, m))
    hlo = eng.spec_hlo()
    hc.assert_no_host_transfers(hlo, "serving draft-verify step")
    nleaves = len(jax.tree_util.tree_leaves(params))
    hc.assert_donates(hlo, range(nleaves, nleaves + eng.n_pool_tensors()),
                      "serving draft-verify step")


def test_spec_disarms_on_sampling(toy, caplog):
    """temperature > 0 breaks the bit-identical-greedy acceptance rule:
    speculation must warn DISARMED (naming sampling) and serve the
    plain decode jit."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    model, params, _ = toy
    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            eng = _engine(model, params, speculative=3, temperature=0.7,
                          top_k=5)
    finally:
        ds_logger.propagate = False
    assert eng.spec_k == 0 and eng._spec is None
    assert any("DISARMED" in r.message and "temperature" in r.message
               for r in caplog.records)


def test_prefix_cache_disarm_blockers(toy, caplog):
    """The cache's DISARM warns name their blockers: an int8-KV ask the
    pool itself disarmed (off-profitability), and a draining engine
    whose closed admission could never consult the tree."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    cfg = GPT2Config(vocab_size=32, n_positions=32, n_embd=8, n_layer=1,
                     n_head=2, dtype=jnp.bfloat16, loss_chunk_tokens=0)
    model = GPT2Model(cfg)
    ids = np.random.default_rng(0).integers(0, 32, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids, "labels": ids})
    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            eng = InferenceEngine(model, params, max_slots=2,
                                  kv_block_size=4, prefill_chunk=8,
                                  max_blocks_per_seq=4,
                                  quantize_kv=True, prefix_cache=True)
    finally:
        ds_logger.propagate = False
    assert not eng.pool.quantized and not eng.prefix_cache
    assert any("DISARMED" in r.message and "int8" in r.message
               for r in caplog.records)

    model3, params3, _ = toy
    eng3 = _engine(model3, params3)
    eng3.scheduler.draining = True
    caplog.clear()
    ds_logger.propagate = True
    try:
        with caplog.at_level(logging.WARNING):
            armed = eng3._arm_prefix_cache(True, False)
    finally:
        ds_logger.propagate = False
    assert not armed
    assert any("DISARMED" in r.message and "draining" in r.message
               for r in caplog.records)
