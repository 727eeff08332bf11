"""What ``CompiledChain._args`` hands the program: a value that is already a
``jax.Array`` goes on as it is, any other through ``jnp.asarray``, counted
in ``engine.metrics`` as ``engine_args_converted``; missing names raise,
extra keys are dropped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec import compile_chain
from repro.models import cnn


@pytest.fixture(scope="module")
def case():
    """A reduced AlexNet (two chain inputs, the image and the dropout
    mask), its inputs and params as NumPy arrays."""
    chain = cnn.build("AN", reduced=True, batch=1)
    params = compile_chain(chain, backend="jnp").init_params(
        jax.random.PRNGKey(0))
    return (chain, cnn.random_inputs(chain),
            {k: np.asarray(v) for k, v in params.items()})


def _on_device(values):
    return {k: jnp.asarray(v) for k, v in values.items()}


def _converted(eng):
    return eng.metrics.value("engine_args_converted")


def _programs(eng):
    return sum(s["value"] for s in eng.metrics.to_dict()["metrics"].get(
        "engine_programs_compiled", {"series": []})["series"])


def _assert_same(got, want):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_device_arrays_reach_the_program_unconverted(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ins, ps = _on_device(inputs), _on_device(params)
    assert _converted(eng) == 0
    got_ins, got_ps, n = eng._args(ins, ps)
    assert n is None
    assert all(got_ins[k] is ins[k] for k in ins)
    assert all(got_ps[k] is ps[k] for k in ps)
    on_device = eng(ins, ps)
    assert _converted(eng) == 0
    # the same values as NumPy arrays give the same program the same
    # arrays, so the same bits
    _assert_same(on_device, eng(inputs, params))
    assert _converted(eng) == len(inputs) + len(params)


@pytest.mark.parametrize("host_inputs,host_params", [
    (True, False), (False, True), (True, True)])
def test_each_converted_value_is_counted(case, host_inputs, host_params):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ins = inputs if host_inputs else _on_device(inputs)
    ps = params if host_params else _on_device(params)
    want = (len(inputs) if host_inputs else 0) \
        + (len(params) if host_params else 0)
    out = eng(ins, ps)
    assert _converted(eng) == want
    eng(ins, ps)
    assert _converted(eng) == 2 * want
    _assert_same(out, eng(_on_device(inputs), _on_device(params)))
    assert _converted(eng) == 2 * want


def test_lists_and_scalars_are_converted(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    mask = next(n for n in chain.inputs if n != "x")
    ins = dict(_on_device(inputs), **{mask: inputs[mask].tolist()})
    _assert_same(eng(ins, _on_device(params)),
                 eng(_on_device(inputs), _on_device(params)))
    assert _converted(eng) == 1


@pytest.mark.parametrize("kind", ["input", "param"])
@pytest.mark.parametrize("on_device", [False, True])
def test_a_missing_name_raises(case, kind, on_device):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ins, ps = (_on_device(inputs), _on_device(params)) if on_device \
        else (dict(inputs), dict(params))
    given = ins if kind == "input" else ps
    name = sorted(given)[-1]
    del given[name]
    with pytest.raises(ValueError, match=f"^missing chain {kind} {name!r}$"):
        eng(ins, ps)
    assert _programs(eng) == 0


def test_no_params_reads_as_missing(case):
    chain, inputs, _params = case
    eng = compile_chain(chain, backend="jnp")
    with pytest.raises(ValueError, match="^missing chain param "):
        eng(_on_device(inputs))


def test_extra_keys_are_dropped_without_a_rebuild(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ins, ps = _on_device(inputs), _on_device(params)
    want = eng(ins, ps)
    assert _programs(eng) == 1
    extra_ins = dict(ins, unused=jnp.ones((3,)), also_unused=np.zeros(2))
    extra_ps = dict(ps, unused=jnp.ones((5, 5)), note="not an array")
    _assert_same(eng(extra_ins, extra_ps), want)
    assert _programs(eng) == 1
    assert _converted(eng) == 0
    got_ins, got_ps, _n = eng._args(extra_ins, extra_ps)
    assert set(got_ins) == set(chain.inputs)
    assert set(got_ps) == set(chain.params)


def test_batch_extended_device_inputs_take_the_bucket_path(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ps = _on_device(params)
    rows = [dict(inputs, x=inputs["x"] * (j + 1)) for j in range(3)]
    batch = {k: jnp.stack([r[k] for r in rows]) for k in inputs}
    _ins, _ps, n = eng._args(batch, ps)
    assert n == 3
    got = eng(batch, ps)
    assert eng.batch_buckets == [4] and eng.batch_compiles == 1
    assert _converted(eng) == 0
    for j, row in enumerate(rows):
        one = eng(_on_device(row), ps)
        for o in one:
            np.testing.assert_allclose(np.asarray(got[o][j]),
                                       np.asarray(one[o]), rtol=1e-5,
                                       atol=1e-5, err_msg=f"row {j} {o}")


def test_an_unjitted_engine_runs_numpy_values(case):
    chain, inputs, params = case
    eager = compile_chain(chain, backend="jnp", jit=False)
    got = eager(inputs, params)
    assert _converted(eager) == len(inputs) + len(params)
    want = compile_chain(chain, backend="jnp")(inputs, params)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


def test_tracers_pass_through(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp")
    ins, ps = _on_device(inputs), _on_device(params)
    traced = jax.jit(lambda i, p: eng(i, p))(ins, ps)
    assert _converted(eng) == 0
    want = eng(ins, ps)
    for k in want:
        np.testing.assert_allclose(np.asarray(traced[k]),
                                   np.asarray(want[k]), rtol=1e-5,
                                   atol=1e-5, err_msg=k)


def test_a_timed_call_counts_the_same(case):
    chain, inputs, params = case
    eng = compile_chain(chain, backend="jnp", profile=True)
    eng(_on_device(inputs), _on_device(params))
    assert _converted(eng) == 0
    eng(inputs, _on_device(params))
    assert _converted(eng) == len(inputs)
    assert eng.metrics.value("engine_timed_calls") == 2
