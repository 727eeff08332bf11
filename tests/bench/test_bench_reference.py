"""Each configuration's plain reference (bench/reference/) against the
program at full width, on the CPU, where both compute in exact float32."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec, weights
from bench.run import _check_against_reference
from repro.exec import compile_chain
from repro.models import cnn

# float32 on both sides, summed in different orders: relative logit error
# of the order of 1e-7 (measured ~3e-7 to 5e-7); a wrong layer reads > 1e-2
TOL = 1e-5


@pytest.mark.parametrize("config", ["mobilenet-v1", "googlenet"])
def test_reference_agrees_with_the_jnp_plan_at_full_width(config):
    cfg = spec.config(config)
    ref = spec.reference(config)
    chain = cnn.build(cfg["net"], batch=2)
    _check_against_reference(chain, ref, 2)
    params = weights.make_params(ref.param_specs(), cfg["weights"], 3)
    inputs = {n: jnp.full(chain.inputs[n].shape, v, jnp.float32)
              for n, v in cfg["fills"].items()}
    inputs["x"] = weights.make_images((2, 3, 224, 224), 1, 3)[0]
    with jax.default_matmul_precision("highest"):
        eng = compile_chain(chain, backend="jnp", lint="off")
        probs = next(iter(eng(inputs, params).values()))
        logits = jax.jit(functools.partial(ref.logits,
                                           precision="highest"))(params,
                                                                 inputs)
    r = check.readings(np.asarray(probs), np.asarray(logits))
    assert r["logit_rel_rms"] < TOL
    # the recipe keeps the softmax away from saturation
    assert float(np.std(np.asarray(logits))) > 0.3
    assert float(np.max(np.asarray(probs))) < 0.5


@pytest.mark.parametrize("config,batch", [("mobilenet-v1", 32),
                                          ("googlenet", 32),
                                          ("googlenet", 1)])
def test_reference_declares_the_chain_inputs_and_parameters(config, batch):
    cfg = spec.config(config)
    _check_against_reference(cnn.build(cfg["net"], batch=batch),
                             spec.reference(config), batch)


def test_a_changed_layer_is_refused():
    ref = spec.reference("googlenet")
    chain = cnn.build("MN", batch=2)
    with pytest.raises(spec.SpecError, match="parameters differ"):
        _check_against_reference(chain, ref, 2)


def test_weights_keep_every_bit_of_a_wide_seed():
    a = weights.key(5)
    b = weights.key(5 + 2 ** 33)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    specs = {"w": ((1, 8, 3, 3), "w", 72), "b": ((1, 8, 1, 1), "b", 72)}
    recipe = {"w_gain": 2.0, "b_std": 0.1}
    p1 = weights.make_params(specs, recipe, 2 ** 31 + 7)
    p2 = weights.make_params(specs, recipe, 2 ** 31 + 7)
    assert all(np.array_equal(p1[k], p2[k]) for k in specs)
    assert float(np.std(np.asarray(p1["w"]))) == pytest.approx(
        (2.0 / 72) ** 0.5, rel=0.5)
