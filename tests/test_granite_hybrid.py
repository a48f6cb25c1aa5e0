"""granite-4.0-h-small (GraniteMoeHybrid) on the serving path, at a
reduced size that keeps its structure: a period with attention inside
it, Mamba-2 with the gated norm, NoPE attention, held experts fewer than
the routed ones, top-k above 1, the shared expert and granite's four
multipliers. Compared in float32 with the plain reference
(``repro.models.reference.granite_hybrid``); the tolerance is float32
rounding (both compute the same sums in different orders: the chunked
or recurrent SSM, the sort-based expert dispatch)."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.armpool import DecodeArmEngine, build_arm_engines, compile_pool
from repro.configs import get_config
from repro.data.routerbench import generate_routerbench
from repro.experiments.spec import ArmPoolSpec
from repro.models import model as MODEL
from repro.models import moe as MOE
from repro.models.reference import granite_hybrid as REF
from repro.serving.batcher import left_align
from repro.serving.engine import ServingEngine

TOL = 2e-5   # widest logit gap over the widest reference logit, float32


def _cfg(**upd):
    """The reduced model, 2 of its 4 experts held (from id 1)."""
    cfg = dataclasses.replace(get_config("granite_4_0_h_small").reduced(),
                              dtype="float32").chip_share(3, 2, 1)
    return dataclasses.replace(cfg, **upd)


def _err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_reduced_config_keeps_the_structure():
    cfg = _cfg()
    assert cfg.attn_every == 3 and cfg.attn_position == 1
    assert [cfg.layer_is_attn(i) for i in range(3)] == [False, True, False]
    assert 0 < cfg.experts_held < cfg.num_experts
    assert cfg.experts_per_token > 1 and cfg.shared_expert_ff > 0
    assert not cfg.use_rope and cfg.moe_capacity_factor == 0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12.0, 0.22, 0.0078125, 16.0)
    assert "norm" in MODEL.init_params(jax.random.PRNGKey(0), cfg)[
        "blocks"]["mamba"]


def test_published_parameter_counts():
    """The catalog's 32B-A9B; the chip share holds 2.415 B."""
    cfg = get_config("granite-4.0-h-small")
    assert cfg.param_count() == pytest.approx(32.2e9, rel=0.01)
    assert cfg.active_param_count() == pytest.approx(8.8e9, rel=0.01)
    assert cfg.chip_share(10, 9, 0).param_count() == pytest.approx(
        2.415e9, rel=1e-3)
    assert [i for i in range(40) if cfg.layer_is_attn(i)] == [5, 15, 25, 35]
    with pytest.raises(ValueError):
        cfg.chip_share(12, 9, 0)          # not whole periods
    with pytest.raises(ValueError):
        cfg.chip_share(10, 9, 66)         # past the last expert


def test_prefill_then_decode_matches_the_reference():
    """Prefill through the cache, then decoding: every position's logits
    against the reference's forward pass over the same tokens."""
    cfg = _cfg()
    eng = ServingEngine(cfg, seed=3, max_seq=32)
    toks = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 9))
    got = {}
    new, _ = eng.generate(toks.astype(np.int32), max_new=5,
                          on_step=lambda i, lg: got.__setitem__(
                              i, np.asarray(lg)))
    new = np.asarray(new)
    for b in range(2):
        seq = np.concatenate([toks[b], new[b, :4]])
        want = np.asarray(REF.forward(eng.params, cfg, seq))
        prog = np.stack([got[i][b, :cfg.vocab_size]
                         for i in range(len(seq))])
        assert _err(prog, want) < TOL


def test_forward_matches_the_reference():
    cfg = _cfg()
    params = MODEL.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                              cfg.vocab_size)
    logits, _ = MODEL.forward_train(params, cfg, {"tokens": toks})
    want = np.asarray(REF.forward(params, cfg, np.asarray(toks[0])))
    assert _err(np.asarray(logits[0, :, :cfg.vocab_size]), want) < TOL


def test_expert_shares_sum_to_the_uncut_layer():
    """Each chip's share computes its experts' part; the parts of all
    shares, with the shared expert (which every chip computes alike)
    counted once, add up to the reference's whole layer."""
    full = _cfg(experts_held=0, expert_first=0)
    E = full.num_experts
    key = jax.random.PRNGKey(4)
    p = MOE.init_moe(key, full)
    shared = MODEL.L.init_ffn(jax.random.PRNGKey(5), full,
                              full.shared_expert_ff)
    h = jax.random.normal(jax.random.PRNGKey(6), (3, 5, full.d_model))
    total = 0.0
    for first in range(0, E, 2):
        share = full.chip_share(full.num_layers, 2, first)
        ps = dict(p, **{k: p[k][first:first + 2] for k in ("wg", "wu",
                                                           "wd")})
        part, _ = MOE.moe_ffn(ps, share, h)
        total = total + part
    total = total + MODEL.L.ffn(shared, h)
    ref_p = {k: np.asarray(v) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want = [REF.moe(ref_p, full, h[i], (0, E))
                + REF._swiglu(shared, h[i]) for i in range(3)]
    assert _err(np.asarray(total), np.stack(want)) < TOL


def test_mixed_length_rows_match_their_solo_runs():
    """Rows of different prompt lengths in one batch: each row's logits
    at each of its positions, and its tokens, are those of the row run
    alone (pads leave the state untouched)."""
    for cfg in (_cfg(), dataclasses.replace(
            get_config("mamba2_130m").reduced(), dtype="float32")):
        eng = ServingEngine(cfg, seed=7, max_seq=32)
        rng = np.random.default_rng(1)
        lengths = [3, 8, 5]
        rows = [rng.integers(1, cfg.vocab_size, n) for n in lengths]
        right = np.zeros((3, 8), np.int32)
        for i, r in enumerate(rows):
            right[i, :len(r)] = r
        toks, start = left_align(right, lengths)
        got = {}
        new, _ = eng.generate(toks, max_new=4, start=start,
                              on_step=lambda i, lg: got.__setitem__(
                                  i, np.asarray(lg)))
        for b, r in enumerate(rows):
            solo = {}
            s_new, _ = eng.generate(r[None].astype(np.int32), max_new=4,
                                    on_step=lambda i, lg: solo.__setitem__(
                                        i, np.asarray(lg)))
            np.testing.assert_array_equal(np.asarray(new)[b],
                                          np.asarray(s_new)[0])
            for j in range(len(r) + 3):
                np.testing.assert_allclose(got[int(start[b]) + j][b],
                                           solo[j][0], rtol=1e-5,
                                           atol=1e-6)


def test_left_align():
    right = np.array([[1, 2, 0, 0], [3, 4, 5, 6], [7, 0, 0, 0]])
    toks, start = left_align(right, [2, 4, 1])
    np.testing.assert_array_equal(toks, [[0, 0, 1, 2], [3, 4, 5, 6],
                                         [0, 0, 0, 7]])
    np.testing.assert_array_equal(start, [2, 0, 3])


def test_build_arm_engines_serves_a_local_arm_at_its_cut(monkeypatch):
    """An arm named as local decodes for real at its chip share whatever
    its size; the pool still costs it from its published configuration."""
    import repro.armpool.serving as AS
    monkeypatch.setattr(AS, "get_config", lambda name: _cfg(
        experts_held=0, expert_first=0) if "granite" in name
        else get_config(name))
    aspec = ArmPoolSpec(arms=("granite_4_0_h_small", "mistral_nemo_12b"),
                        mapping=(("granite_4_0_h_small", "yi-34b-chat"),),
                        reduced_decode=False, latency_scale=0.0,
                        local=(("granite_4_0_h_small",
                                (("num_layers", 3), ("experts_held", 2),
                                 ("expert_first", 2))),))
    pool = compile_pool(aspec, generate_routerbench(0, 300))
    assert pool.params_b[0] == pytest.approx(32.2, rel=0.01)
    engines, info = build_arm_engines(pool, aspec, max_batch=2)
    eng = engines[0]
    assert isinstance(eng, DecodeArmEngine) and eng.index == 0
    assert (eng.cfg.num_layers, eng.cfg.experts_held,
            eng.cfg.expert_first) == (3, 2, 2)
    assert eng.cfg.num_experts == 4 and eng.decode_steps == 0
    assert info["real_decode_arms"] == ["granite_4_0_h_small"]
    assert info["roofline_clocked_arms"] == ["mistral_nemo_12b"]
    new, steps = eng.generate(np.array([[5, 6, 0], [7, 8, 9]]), max_new=2,
                              lengths=[2, 3])
    assert new.shape == (2, 2) and steps == 1
    with pytest.raises(ValueError):
        ArmPoolSpec(arms=("mistral_nemo_12b",),
                    local=(("granite_4_0_h_small", ()),))


def test_local_arms_survive_the_spec_json_round_trip():
    from repro.experiments.spec import (ExperimentSpec, PolicySpec,
                                        spec_from_json, spec_to_json)
    pool = ArmPoolSpec(arms=("granite_4_0_h_small",),
                       local=(("granite_4_0_h_small",
                               (("num_layers", 10), ("experts_held", 9))),))
    spec = ExperimentSpec(name="pool", policies=(PolicySpec("neuralucb"),),
                          armpool=pool)
    doc = spec_to_json(spec)
    assert doc["armpool"]["local"] == [
        ["granite_4_0_h_small", [["num_layers", 10], ["experts_held", 9]]]]
    assert spec_from_json(json.loads(json.dumps(doc))) == spec
    bare = spec_to_json(dataclasses.replace(
        spec, armpool=dataclasses.replace(pool, local=())))
    assert "local" not in bare["armpool"]


def test_build_arm_engines_draws_the_weights_from_its_seed(monkeypatch):
    """``seed`` sets the real arms' weights; without it they come from
    the pool's checksum, as before."""
    import repro.armpool.serving as AS
    monkeypatch.setattr(AS, "get_config", lambda name: _cfg(
        experts_held=0, expert_first=0) if "granite" in name
        else get_config(name))
    aspec = ArmPoolSpec(arms=("granite_4_0_h_small", "mistral_nemo_12b"),
                        mapping=(("granite_4_0_h_small", "yi-34b-chat"),),
                        reduced_decode=False, latency_scale=0.0,
                        local=(("granite_4_0_h_small",
                                (("num_layers", 3),)),))
    pool = compile_pool(aspec, generate_routerbench(0, 300))

    def embed(**kw):
        engines, _ = build_arm_engines(pool, aspec, max_batch=1, **kw)
        return np.asarray(engines[0].engine.params["embed"])

    a, b = embed(seed=11), embed(seed=12)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, embed(seed=11))
    np.testing.assert_array_equal(
        embed(), embed(seed=int(pool.checksum % 997)))


def test_generation_hands_over_its_last_cache_and_bounds_the_queue():
    """``on_cache`` sees the cache after the last step fed (the prompt
    and every new token but the last), and after each step the engine
    has waited for all but the last ``IN_FLIGHT - 1`` steps' outputs."""
    import repro.serving.engine as E
    eng = ServingEngine(_cfg(), seed=3, max_seq=16)
    waiting, seen = [], {}
    eng.generate(np.array([[4, 5, 6], [0, 7, 8]]), max_new=3,
                 start=[0, 1], on_cache=lambda c: seen.update(c),
                 on_step=lambda i, lg: waiting.append(len(eng._flight)))
    assert int(seen["pos"]) == 3 + 3 - 1
    np.testing.assert_array_equal(np.asarray(seen["start"]), [0, 1])
    assert len(waiting) == 3 + 3 - 1
    assert max(waiting) == E.IN_FLIGHT - 1
