"""The serving check: the one rule the harness applies (``judge_rows``), on
hand-made rows and on a routed stand-in written here in plain jax.numpy; what
an architecture's file owes the serving driver (``served_check``,
``reference_logits`` with ``rows``); and a run whose timed path is broken
underneath, which has to come out as not correct.  CPU, seconds."""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
REHEARSAL = os.path.join(HERE, "cells")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402


@pytest.fixture(scope="module")
def serve():
    return cells.load_module(os.path.join(BENCH_DIR, "harness",
                                          "drive_serve.py"), "drive_serve_c")


@pytest.fixture(scope="module")
def gpt2():
    return cells.load_module(os.path.join(BENCH_DIR, "architectures",
                                          "gpt2.py"), "bench_arch_gpt2_c")


# ---------------------------------------------------------------------------
# judge_rows on hand-made rows
# ---------------------------------------------------------------------------
SPACING = 2.0 ** -6         # of bf16 at a best logit in [2, 4)


def _rows(gaps_in_spacings):
    """One row a gap: eight logits, best 2.5 at id 0, the served token
    (id 1) that many bf16 spacings under it."""
    logits = np.array([[2.5, 2.5 - gap * SPACING, 0.9, 0.3, 0.0, -0.3, -0.9,
                        -1.2] for gap in gaps_in_spacings],
                      np.float32).reshape(-1, 8)
    return logits, np.ones(len(logits), np.int64)


EVERY_ROW = {"near_best_spacings": 4.0, "share": 1.0,
             "every_row_sigma": None}
MOST_ROWS = {"near_best_spacings": 4.0, "share": 0.95,
             "every_row_sigma": None}


@pytest.mark.parametrize("gaps,rule,held", [
    ([0.0, 1.0, 3.9], EVERY_ROW, True),
    ([0.0, 1.0, 4.1], EVERY_ROW, False),
    # share 0.95 of twenty rows: one may lie further out, two may not
    ([4.1] + [1.0] * 19, MOST_ROWS, True),
    ([4.1, 4.1] + [1.0] * 18, MOST_ROWS, False),
    ([4.1] + [1.0] * 19, EVERY_ROW, False),
    ([], EVERY_ROW, False),           # nothing judged holds nothing
], ids=["3.9_spacings_every_row_held", "4.1_spacings_every_row_refused",
        "one_of_twenty_over_share_0.95_held",
        "two_of_twenty_over_share_0.95_refused",
        "one_of_twenty_over_share_1.0_refused", "no_rows_refused"])
def test_judge_rows_by_hand(serve, gaps, rule, held):
    logits, served = _rows(gaps)
    got, seen = serve.judge_rows(logits, served, rule)
    assert got is held, seen
    assert seen["rows_judged"] == len(gaps)
    if gaps:
        assert seen["worst_spacings_below_best"] == pytest.approx(max(gaps),
                                                                  abs=1e-3)
        assert seen["share_within"] == pytest.approx(
            np.mean(np.array(gaps) <= 4.0))


@pytest.mark.parametrize("of_bound,held", [(0.9, True), (1.1, False)],
                         ids=["held", "refused"])
def test_judge_rows_a_row_beyond_every_row_sigma(serve, of_bound, held):
    """Nineteen rows near their best and one far out, which the share lets
    through: the second bound decides, set here to the far row's own
    reading (its gap over its row's sigma) over ``of_bound``."""
    logits, served = _rows([1.0] * 19 + [64.0])
    far = logits[-1]
    reading = (far[0] - far[1]) / far.std()
    got, seen = serve.judge_rows(
        logits, served, dict(MOST_ROWS, every_row_sigma=reading / of_bound))
    assert seen["worst_sigma_below_best"] == pytest.approx(reading, rel=1e-5)
    assert seen["share_within"] == 0.95
    assert got is held, seen


# ---------------------------------------------------------------------------
# a routed stand-in: bf16 against f32 "highest", both written here
# ---------------------------------------------------------------------------
# Three layers of causal attention (4 heads of 32) and a routed FFN: 64
# SwiGLU experts of width 32, 8 a token by a sigmoid score, their weights
# renormalised over the 8 and scaled by 2.5, beside one shared expert;
# RMSNorm, hidden 128, 512 ids, 8 sequences of 40 = 320 rows.  Weights are
# rounded to bf16 on both sides.  The low side rounds the inputs and the
# result of every matmul to bf16 (f32 accumulation); the reference is f32.
# The "served" token of a row is the low side's best, as greedy decoding
# picks it; it is judged under the reference's logits of the same row.
V, E, L, H, D = 512, 128, 3, 4, 32
NE, K, F, SCALE = 64, 8, 32, 2.5
B, S = 8, 40


def _weights(seed):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8 * L + 2))

    def w(*shape, fan_in=None):
        w = jax.random.normal(next(keys), shape) \
            / np.sqrt(fan_in or shape[-2])
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    return {"emb": w(V, E, fan_in=1), "head": w(E, V),
            "layers": [{"qkv": w(E, 3 * E), "o": w(E, E),
                        "router": w(E, NE), "up": w(NE, E, 2 * F),
                        "down": w(NE, F, E), "shared_up": w(E, 2 * F),
                        "shared_down": w(F, E)} for _ in range(L)]}


def _cut(x, bits):
    """Round to ``bits`` significand bits."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(w, ids, low, fault):
    """``low``: the bf16 side.  ``fault``: what the bf16 side does wrongly
    (None: nothing)."""
    def keep(x):
        if not low:
            return x
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        # 8 significand bits less 4: about fp8's
        return _cut(x, 4) if fault == "activations_4_bits_fewer" else x

    def mm(spec, a, b):     # products of bf16 values are exact in f32
        return keep(jnp.einsum(spec, keep(a), b, precision="highest"))

    def norm(x):
        return keep(x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-6))

    def glu(h):
        gate, up = jnp.split(h, 2, -1)
        return keep(jax.nn.silu(gate) * up)

    x = keep(w["emb"][ids])
    for p in w["layers"]:
        h = norm(x)
        q, k, v = (t.reshape(B, S, H, D) for t in
                   jnp.split(mm("bse,ef->bsf", h, p["qkv"]), 3, -1))
        if fault == "keys_shifted_by_one":      # a cache position misread
            k = jnp.roll(k, 1, axis=1)
        s = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        a = mm("bhqk,bkhd->bqhd", keep(jax.nn.softmax(s, -1)), v)
        x = keep(x + mm("bse,ef->bsf", a.reshape(B, S, E), p["o"]))
        h = norm(x)
        score = jax.nn.sigmoid(mm("bse,en->bsn", h, p["router"]))
        top, chosen = jax.lax.top_k(score, K)
        weight = top / top.sum(-1, keepdims=True)
        if fault != "routed_sum_not_scaled":
            weight = SCALE * weight
        per_expert = keep(jnp.sum(
            jax.nn.one_hot(chosen, NE) * weight[..., None], -2))
        y = mm("bsnf,nfe->bsne", glu(mm("bse,nef->bsnf", h, p["up"])),
               p["down"])
        x = x + keep(jnp.einsum("bsne,bsn->bse", y, per_expert,
                                precision="highest"))
        if fault != "no_shared_expert":
            x = x + mm("bsf,fe->bse",
                       glu(mm("bse,ef->bsf", h, p["shared_up"])),
                       p["shared_down"])
        x = keep(x)
    return mm("bse,ev->bsv", norm(x), w["head"])


# What the stand-in reads over seeds 0-15 (this sandbox's CPU, 320 rows a
# seed), sound bf16 side: 93.4-98.4 % of rows within 4 spacings, worst row
# 24-136 spacings = 0.38-2.09 of its row's sigma under the reference's best
# (the bf16 side picks another set of eight experts in some token-layers;
# most rows do not notice, a few become other rows altogether).  Without
# the routing (the test below) the worst row lies 0.58-2.15 spacings under.
# With a fault: within 4 spacings 20-28 % (keys), 14-23 % (no shared
# expert), 45-53 % (2.5 left off), 60-68 % (activations cut to 4 bits);
# worst row 2.9-4.0, 3.1-4.6, 1.4-2.2, 1.0-2.3 sigma.  A token picked
# blindly lies ~3 sigma under at 512 ids.  So: share 0.90, above every
# faulty reading (0.68) and under every sound one (0.934), which refuses
# all four alone; every row within 2.5 sigma, over the sound side's worst
# (2.09) and under the first two faults' least (2.86): it is there for a
# fault in a few rows, which the share would let through.  These are the
# stand-in's numbers, not a model's.
ROUTED = {"near_best_spacings": 4.0, "share": 0.90, "every_row_sigma": 2.5}
SEEDS = (0, 1, 2)


@pytest.fixture(scope="module")
def standin_rows(serve):
    """fault -> per seed, (spacings, sigmas) of every row."""
    @functools.lru_cache(maxsize=None)
    def rows(fault):
        out = []
        for seed in SEEDS:
            w = _weights(seed)
            ids = jax.random.randint(jax.random.PRNGKey(seed + 1000),
                                     (B, S), 0, V)
            reference = np.asarray(_logits(w, ids, False, None))
            served = np.asarray(_logits(w, ids, True, fault)).argmax(-1)
            out.append(serve.row_gaps(reference.reshape(B * S, V),
                                      served.reshape(B * S)))
        return out
    return rows


@pytest.mark.parametrize("fault,rule,held", [
    (None, EVERY_ROW, False),
    (None, ROUTED, True),
    ("keys_shifted_by_one", ROUTED, False),
    ("no_shared_expert", ROUTED, False),
    ("routed_sum_not_scaled", ROUTED, False),
    # the control: the nearest precision under bf16.  It separates (60-68 %
    # within 4 spacings against 93-98 %), so it is held, not only reported
    ("activations_4_bits_fewer", ROUTED, False),
], ids=["every_row_rule_refuses_the_sound_routed_side",
        "routed_rule_passes_the_sound_routed_side",
        "routed_rule_refuses_keys_shifted_by_one",
        "routed_rule_refuses_no_shared_expert",
        "routed_rule_refuses_routed_sum_not_scaled",
        "routed_rule_refuses_activations_4_bits_fewer"])
def test_routed_standin(serve, standin_rows, fault, rule, held):
    for seed, (spacings, sigmas) in zip(SEEDS, standin_rows(fault)):
        got, seen = serve.judge_gaps(spacings, sigmas, rule)
        print(fault, seed, seen)
        assert got is held, (seed, seen)
        assert seen["rows_judged"] == B * S


def test_a_dense_standin_holds_the_every_row_rule(serve):
    """The same frame without the routing (every token through the shared
    expert alone): every row within 4 spacings, as GPT-2's rule says of a
    dense model, so what the routed side misses it by is the routing."""
    for seed in SEEDS:
        w = _weights(seed)
        for p in w["layers"]:
            p["down"] = jnp.zeros_like(p["down"])
        ids = jax.random.randint(jax.random.PRNGKey(seed + 1000), (B, S),
                                 0, V)
        reference = np.asarray(_logits(w, ids, False, None))
        served = np.asarray(_logits(w, ids, True, None)).argmax(-1)
        got, seen = serve.judge_rows(reference.reshape(B * S, V),
                                     served.reshape(B * S), EVERY_ROW)
        assert got, (seed, seen)


# ---------------------------------------------------------------------------
# what gpt2.py owes the serving driver
# ---------------------------------------------------------------------------
def _tiny():
    with open(os.path.join(REHEARSAL, "configs", "gpt2-tiny.json")) as f:
        return json.load(f)


def test_gpt2_served_check_states_todays_rule(gpt2):
    config = _tiny()
    check = gpt2.served_check(config)
    assert check["rule"] == {"near_best_spacings": 4.0, "share": 1.0,
                             "every_row_sigma": None}
    assert set(check["why"]) == set(check["rule"])
    assert check["width"](5) == check["width"](64) == config["n_positions"]


@pytest.mark.parametrize("head_rows", [128, 8],
                         ids=["one_block", "three_blocks_of_8"])
def test_reference_logits_of_rows_are_the_rows_of_the_full_call(
        gpt2, monkeypatch, head_rows):
    config = _tiny()
    params = gpt2.init_params(
        gpt2.build_model(config, {"scan_layers": True}), seed=5)
    weights = gpt2.reference_weights(params, config)
    ids = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, config["n_positions"]), dtype=np.int32)
    full = np.asarray(gpt2.reference_logits(weights, config, ids))
    assert full.shape == (2, config["n_positions"], config["vocab_size"])
    monkeypatch.setattr(gpt2, "_HEAD_ROWS", head_rows)
    rows = np.arange(7, 27)         # 20 rows: a block of 8 does not divide
    some = np.asarray(gpt2.reference_logits(weights, config, ids, rows))
    assert some.shape == (2, 20, config["vocab_size"])
    np.testing.assert_allclose(some, full[:, rows], rtol=0, atol=1e-5)


@pytest.mark.parametrize("bits,held", [(8, True), (4, False)],
                         ids=["as_bf16_held", "control_as_fp8_refused"])
def test_gpt2_rule_refuses_its_control_at_a_toy_width(gpt2, serve, bits,
                                                      held):
    """The control of GPT-2's rule, at ``gpt2-tiny``: the reference itself
    with every matmul's inputs and result rounded to 4 significand bits
    (about fp8, the nearest precision under the configuration's bf16) and
    its best token of every row taken as the served one.  Seeds 0-5 here:
    worst row 26-48 spacings under the reference's best, against 0.3-1.6
    with 8 bits (bf16's), and a limit of 4.  On the chip at the cells' own
    size: ``benchmark/tools/served_control.py`` (PERF.md section 6)."""
    config = _tiny()
    rule = gpt2.served_check(config)["rule"]
    for seed in range(3):
        params = gpt2.init_params(
            gpt2.build_model(config, {"scan_layers": True}), seed)
        weights = gpt2.reference_weights(params, config)
        ids = np.random.default_rng(seed).integers(
            0, config["vocab_size"], (4, config["n_positions"]),
            dtype=np.int32)
        reference = np.asarray(gpt2.reference_logits(weights, config, ids))
        low = np.asarray(gpt2.reference_logits(weights, config, ids,
                                               control_bits=bits))
        got, seen = serve.judge_rows(
            reference.reshape(-1, config["vocab_size"]),
            low.argmax(-1).reshape(-1), rule)
        assert got is held, (seed, seen)


# ---------------------------------------------------------------------------
# a run whose timed path is broken underneath comes out as not correct
# ---------------------------------------------------------------------------
def _rehearse(name, devices, log):
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, name, root=REHEARSAL)
    return cell.driver().run(cell, devices, seed=3, seconds=1.0,
                             trace=False, process_start=time.perf_counter(),
                             log=log)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        devices, monkeypatch):
    """Everything of a run but the look for a chip, with every token the
    engine takes up from its programs off by one id."""
    from deepspeed_tpu.serving import InferenceEngine

    take_up = InferenceEngine._on_new_token
    monkeypatch.setattr(
        InferenceEngine, "_on_new_token",
        lambda self, req, token, *a, **k: take_up(
            self, req, (int(token) + 1) % self.cfg.vocab_size, *a, **k))
    logged = {}
    run = _rehearse("gpt2-tiny.serve-tiny-backlog", devices[:1],
                    logged.update)
    assert run["failed"] == 0 and logged["checks"]["no_request_failed"]
    assert not logged["checks"]["served_tokens_hold_to_reference"]
    assert not run["correct"]
    seen, limit = run["compared"]["worst_spacings_below_best"]
    assert seen > limit == 4.0


def test_the_control_tool_reads_program_and_control_in_one_run(devices):
    """``tools/served_control.py`` as the chip runs it, at the rehearsal
    cell: the program is held, the control (the reference in 4 significand
    bits, on the served prompts and tokens) is refused."""
    tool = cells.load_module(os.path.join(
        BENCH_DIR, "tools", "served_control.py"), "served_control_t")
    benchmark = cells.load_benchmark(os.path.join(REHEARSAL,
                                                  "BENCHMARK.json"))
    cell = cells.Cell(benchmark, "gpt2-tiny.serve-tiny-backlog",
                      root=REHEARSAL)
    # three seconds of wall clock: in one, a loaded machine finishes so few
    # requests that the control's worst row can lie inside the limit
    program, control, control_held = tool.readings(
        cell, devices[:1], 3.0, [3], log=lambda line: None)
    assert program[0]["worst_spacings_below_best"] <= 4.0
    assert control[0]["rows_judged"] == program[0]["rows_judged"] > 0
    assert control[0]["worst_spacings_below_best"] > 4.0
    assert control_held == [False]


def test_a_cell_brings_its_own_architecture(devices):
    """``gpt2-wrong-eps`` is found under the test cell's own root: GPT-2's
    file with the program's model built at another LayerNorm epsilon than
    the reference reads from the configuration, so the program serves
    another function and the run is not correct."""
    logged = {}
    run = _rehearse("gpt2-tiny-wrong-eps.serve-tiny-backlog", devices[:1],
                    logged.update)
    assert run["failed"] == 0
    assert not logged["checks"]["served_tokens_hold_to_reference"]
    assert not run["correct"]
