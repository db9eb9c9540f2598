"""CPU checks of the chip benchmark's own parts: the traffic plan, the
yardstick arithmetic, the trace reduction, the reference, and one tiny run
of the whole harness in interpret mode."""

from __future__ import annotations

import json
import math
import pathlib
import time

import numpy as np
import pytest

import flops
import harness
import trace as trace_mod
import traffic

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
CELLS = HERE / "data" / "cells"
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())


def _cells():
    return [w["name"] for w in BENCH["workloads"]]


# --------------------------------------------------------------------------- #
# traffic: every seed does the same work
# --------------------------------------------------------------------------- #


def _shapes(cell: str, seed: int):
    """What a run offers before any reply: start times, lengths, and the
    token ids of the system prompt and of every turn's new tokens."""
    c = harness.load_json("workloads", cell)
    mix = traffic.load_mix(c["traffic"])
    vocab = harness.load_json("configs", c["config"])["vocab_size"]
    plan = traffic.plan_sessions(mix, c["rate"], c["lead_s"] + BENCH["run_seconds"])
    shapes = [(p.start_s, [(t.new_tokens, t.output_tokens) for t in p.turns]) for p in plan]
    ids = traffic.system_prompt(mix, seed, vocab) + [
        tok
        for p in plan[:4]
        for k, t in enumerate(p.turns)
        for tok in traffic.turn_tokens(seed, p.index, k, t.new_tokens, vocab)
    ]
    return shapes, ids


@pytest.mark.parametrize("cell", _cells())
def test_two_seeds_offer_the_same_work(cell):
    shapes_a, ids_a = _shapes(cell, 5)
    shapes_b, ids_b = _shapes(cell, 2**31 + 17)
    assert shapes_a == shapes_b
    assert len(ids_a) == len(ids_b) and ids_a != ids_b


def test_lengths_sit_at_log_uniform_quantiles():
    q = traffic.log_uniform_quantiles(64, 512, 8)
    want = [round(math.exp(math.log(64) + (i + 0.5) / 8 * math.log(8))) for i in range(8)]
    assert q == want and q == sorted(q)
    order = traffic.dealing_order(64, 40)
    assert sorted(order) == list(range(64))


def test_plan_deals_every_quantile_once_per_round():
    """n consecutive turns take each of the n quantile lengths once, and
    sessions start evenly spaced."""
    mix = traffic.load_mix("agent-8k")
    n = mix.quantile_points
    plan = traffic.plan_sessions(mix, 0.5, n // mix.turns / 0.5)
    turns = [t for p in plan for t in p.turns][:n]
    assert sorted(t.new_tokens for t in turns) == traffic.log_uniform_quantiles(*mix.new_tokens, n)
    assert sorted(t.output_tokens for t in turns) == traffic.log_uniform_quantiles(*mix.output_tokens, n)
    assert np.allclose(np.diff([p.start_s for p in plan]), 2.0)


@pytest.mark.parametrize("cell", _cells())
def test_decode_warmup_meets_every_batch_and_queue_length(cell):
    """For every live batch b up to max_batch and every total T of block
    counts the plan can give b requests, some layout of the warm-up has the
    same b and pads T to the same queue length, counted past the shared
    system-prompt blocks and over the whole context; and a request's count
    never falls from one layout to the next, as the warm-up can only grow
    a request."""
    from itertools import groupby

    c = harness.load_json("workloads", cell)
    conf = harness.load_json("configs", c["config"])
    mix = traffic.load_mix(c["traffic"])
    plan = traffic.plan_sessions(mix, c["rate"], c["lead_s"] + BENCH["run_seconds"])
    bk, q = conf["serving"]["block_k"], 16
    shared = mix.system_prompt_tokens // bk
    layouts = harness.decode_layouts(mix, plan, bk, q)
    counts = [
        -(-(mix.system_prompt_tokens + sum(x.new_tokens + x.output_tokens for x in p.turns[:k])
            + t.new_tokens + j) // bk) - shared
        for p in plan for k, t in enumerate(p.turns) for j in range(1, t.output_tokens)
    ]
    lo, hi = min(counts), max(counts)
    suffix = {(len(lay), -(-sum(lay) // q)) for lay in layouts}
    whole = {(len(lay), -(-(sum(lay) + len(lay) * shared) // q)) for lay in layouts}
    for b in range(1, mix.max_batch + 1):
        for total in range(b * lo, b * hi + 1):
            assert (b, -(-total // q)) in suffix, (b, total)
            assert (b, -(-(total + b * shared) // q)) in whole, (b, total)
    for _, group in groupby(layouts, key=len):
        group = list(group)
        assert all(1 <= x <= hi for lay in group for x in lay)
        assert all(a <= b for prev, nxt in zip(group, group[1:]) for a, b in zip(prev, nxt))


# --------------------------------------------------------------------------- #
# yardstick arithmetic
# --------------------------------------------------------------------------- #

V2 = harness.load_json("configs", "deepseek-v2-mla")


def test_decode_ops_follow_the_kernel_formula():
    """2 * B * G * S_k * (Dk + Dv) per layer for B requests of S_k keys."""
    b, s_k = 32, 9000
    ops, nbytes = flops.decode_attention(V2, [s_k] * b)
    g, dk, dv, layers = 128, 576, 512, V2["num_hidden_layers"]
    assert ops == 2 * b * g * s_k * (dk + dv) * layers
    assert nbytes == 2 * b * (s_k * dk + g * (dk + dv)) * layers


def test_prefill_ops_sum_each_tokens_keys():
    start, n = 8192, 300
    ops, _ = flops.prefill_attention(V2, start, n)
    want = sum(flops.attention_ops(128, 576, 512, j + 1) for j in range(start, start + n))
    assert ops == want * V2["num_hidden_layers"]


def test_dense_ops_per_token_from_widths():
    d, h, ff = 5120, 128, 12288
    per_layer = d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128 + h * 128 * d + 3 * d * ff
    assert flops.dense_ops_per_token(V2) == 2 * per_layer * 4


def test_unknown_device_has_no_peaks():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")


# --------------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------------- #


def test_reduce_small_trace():
    """Hand-made records: busy is the union of device intervals inside the
    window, gaps carry the host span they started in."""
    rec = {
        "window": [1000, 2000],
        "device": {
            "/device:TPU:0": [
                ["jit_mla_decode_paged_queue_rows", 900, 1100],  # clipped to 1000
                ["jit__paged_layer_post", 1050, 1200],  # overlaps the kernel
                ["jit_dynamic_slice", 1500, 1600],
                ["jit_mla_decode_paged_queue_rows", 1900, 2100],  # clipped to 2000
            ]
        },
        "host": [["step", 1000, 1600], ["finish", 1600, 1800]],
    }
    out = trace_mod.reduce(rec, kernel_names=["mla_decode_paged"])
    assert out["window_s"] == pytest.approx(1e-6)
    assert out["busy_s"] == pytest.approx((200 + 100 + 100) * 1e-9)
    assert out["kernel_s"] == pytest.approx((100 + 100) * 1e-9)
    assert out["idle_gaps"][0] == ["step", pytest.approx(300e-9)]
    assert ["finish", pytest.approx(300e-9)] in out["idle_gaps"]
    assert out["device_ops"][0][0] == "jit_mla_decode_paged_queue_rows"


def test_reduce_recorded_trace():
    """A slice of a trace recorded on a v5e by the traced run: the
    reduction's busy time and kernel time agree with a direct count."""
    rec = json.loads((HERE / "data" / "trace_sample.json").read_text())
    out = trace_mod.reduce(rec, kernel_names=["mla_decode_paged", "combine_"])
    lo, hi = rec["window"]
    (evs,) = rec["device"].values()
    covered = np.zeros(hi - lo, bool)
    for _, s, e in evs:
        covered[max(s, lo) - lo : max(min(e, hi), lo) - lo] = True
    assert out["busy_s"] == pytest.approx(covered.sum() / 1e9)
    kernel = sum(
        min(e, hi) - max(s, lo)
        for n, s, e in evs
        if ("mla_decode_paged" in n or "combine_" in n) and min(e, hi) > max(s, lo)
    )
    assert out["kernel_s"] == pytest.approx(kernel / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]


# --------------------------------------------------------------------------- #
# reference and the whole harness at tiny widths
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny():
    from repro.models.model_zoo import build_model

    conf = harness.load_json("configs", "tiny-mla", CELLS)
    conf["name"] = "tiny-mla"
    family = harness.load_module("families", conf["family"])
    model = build_model(family.program_config(conf))
    return conf, family, model


def test_reference_agrees_with_the_programs_dense_forward(tiny):
    """A second witness: the program's own dense float32 forward, with its
    plain softmax attention, and the reference give the same logits on the
    harness's weights."""
    import dataclasses

    import jax.numpy as jnp

    from repro.models import transformer

    conf, family, model = tiny
    cfg = dataclasses.replace(model.cfg, attn_variant="base", attn_impl="naive")
    params = family.make_params(model, conf, 3)
    tokens = np.random.default_rng(0).integers(2, conf["vocab_size"], 70)
    hidden, _, _ = transformer.lm_apply(
        params, jnp.asarray(tokens)[None], cfg=cfg, dtype=jnp.float32
    )
    want = np.asarray(transformer.lm_logits(params, hidden, cfg=cfg, dtype=jnp.float32))[0]
    got = family.reference_logits(params, conf, tokens, np.arange(70))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    low = family.reference_logits(params, conf, tokens, np.arange(70), int8=True)
    assert np.abs(low - got).max() > 10 * np.abs(want - got).max()


def tiny_run(seed=5, seconds=3.0, hook=None, control=False):
    opts = harness.Options(
        interpret=True, require_chip=False, root=CELLS, bench=BENCH,
        session_hook=hook, control=control,
    )
    return harness.run_cell("tiny-agent", seed, seconds, False, time.monotonic(), opts)


@pytest.fixture(scope="module")
def sound_run():
    return tiny_run(control=True)


def test_sound_run_is_correct_and_compiles_nothing_in_its_window(sound_run):
    result, extra = sound_run
    assert result["correct"] is True
    assert extra["readings"]["checked_tokens"] > 0
    assert extra["window_compiles"] == 0
    assert result["check"]["window_compiles"] == {"value": 0, "limit": 0}
    assert extra["phases"]["decode_warmup_steps"] > 0
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"out_tok_s", "itl_p95_ms", "ttft_p50_ms", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_control_fails_the_limit(sound_run):
    """The int8 control, read at the same positions of the same sample,
    lies past the limit that the program's float32 run stays under, and
    the run's own comparison finds it not correct."""
    import control

    result, extra = sound_run
    assert extra["readings"]["control_gap_max"] > result["check"]["logit_gap_max"]["limit"]
    check, correct = control.control_verdict(extra["readings"], extra["limits"])
    assert correct is False
    assert check["logit_gap_max"]["value"] == extra["readings"]["control_gap_max"]


def test_a_window_that_compiles_is_not_admitted():
    readings = {"logit_gap_max": 0.0, "window_compiles": 1}
    check, correct = harness.judge(readings, {"logit_gap_max": 0.2})
    assert correct is False and check["window_compiles"] == {"value": 1, "limit": 0}
    assert harness.judge({**readings, "window_compiles": 0}, {"logit_gap_max": 0.2})[1] is True


def _alter_a_token(sess):
    """The timed path, broken: in every step, the token one request emits
    is replaced where the step produces it, and decoding goes on from the
    replaced token."""
    step = sess.step

    def broken():
        step()
        for rid in sess.active[:1]:
            tok = (sess.outputs[rid][-1] + 1) % sess.cfg.vocab_size
            sess.outputs[rid][-1] = tok
            sess.last_token[rid] = tok

    sess.step = broken


def _drop_cache_writes(sess):
    """The timed path, broken: the decode step leaves the page pool as it
    was (no token's latent row is written)."""
    sess.cache.write_layer_tokens = lambda *a, **k: None


@pytest.mark.parametrize("fault", [_alter_a_token, _drop_cache_writes])
def test_a_broken_timed_path_is_not_correct(fault):
    result, extra = tiny_run(seed=9, hook=fault)
    assert result["correct"] is False
    assert extra["readings"]["logit_gap_max"] > result["check"]["logit_gap_max"]["limit"]
