"""The numbers that decide ``correct`` (bench/check.py), on made-up logits."""
import numpy as np
import pytest

from bench import check


def _probs(logits):
    return np.exp(check.log_softmax(logits))


def _logits(images=64, seed=0):
    return np.random.default_rng(seed).normal(0.0, 1.5, (images, 1000))


def _noisy(ref, scale, seed=1):
    return ref + scale * np.random.default_rng(seed).normal(size=ref.shape)


def test_an_exact_answer_reads_float64_rounding():
    ref = _logits()
    r = check.readings(_probs(ref), ref)
    assert r["logit_rel_rms"] < 1e-12
    assert r["logit_rel_gm"] == pytest.approx(check.FLOOR)


@pytest.mark.parametrize("scale", [1.5e-4, 1.5e-3, 1.5e-2])
def test_noise_on_every_image_reads_its_size_in_both_numbers(scale):
    ref = _logits()
    r = check.readings(_probs(_noisy(ref, scale)), ref)
    assert r["logit_rel_rms"] == pytest.approx(scale / 1.5, rel=0.05)
    assert r["logit_rel_gm"] == pytest.approx(scale / 1.5, rel=0.05)


def test_one_bit_exact_image_does_not_pull_the_mean_to_nought():
    ref = _logits()
    out = _noisy(ref, 1.5e-3)
    out[0] = ref[0]
    r = check.readings(_probs(out), ref)
    assert r["logit_rel_gm"] > 0.8e-3


def test_one_altered_answer_moves_the_root_mean_square():
    ref = _logits(images=256)
    out = _noisy(ref, 1.5e-4)
    out[3] = np.roll(ref[3], 1)
    r = check.readings(_probs(out), ref)
    assert r["logit_rel_rms"] > 0.05
    assert r["logit_rel_gm"] < 1e-3


@pytest.mark.parametrize("bad", [np.nan, np.inf, 0.0, -1.0])
def test_an_output_that_is_not_a_probability_fails_everything(bad):
    ref = _logits(images=4)
    p = _probs(ref)
    p[2, 7] = bad
    r = check.readings(p, ref)
    assert r["logit_rel_rms"] == r["logit_rel_gm"] == float("inf")
    assert not check.passed(check.verdict(r, {"logit_rel_gm": 1.0}))


def test_a_limit_the_readings_do_not_have_fails():
    assert not check.passed(check.verdict({}, {"logit_rel_rms": 1.0}))
