"""The engine's instrumentation inside the one fused program: a named scope
per fusion-group step that ``op_steps()`` reads back from the compiled
module, and build counters that fire once per program built
(``repro.obs.compiles``)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interpreter import init_chain_params
from repro.exec import compile_chain
from repro.exec.engine import hlo_op_steps
from repro.models import cnn
from repro.obs import compiles

_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """Compile every program here: another test of the process may have
    turned the persistent cache on, and an entry there (its key ignores
    the metadata) would answer a compile with another program's text and
    count as a cache hit."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _case(net):
    chain = cnn.build(net, reduced=True, batch=1)
    params = init_chain_params(chain, jax.random.PRNGKey(0))
    return chain, cnn.random_inputs(chain), params


def _specs(eng):
    def spec(infos):
        return {n: jax.ShapeDtypeStruct(i.shape, jnp.float32)
                for n, i in infos.items()}
    return spec(eng.chain.inputs), spec(eng.chain.params)


def _entry(text):
    body = text[text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    return [_INST.match(ln).group(1) for ln in body.splitlines()
            if _INST.match(ln) and " parameter(" not in ln]


@pytest.mark.parametrize("net", ["MN", "GLN"])
def test_op_steps_puts_the_program_down_to_its_steps(net):
    chain, _inputs, _params = _case(net)
    eng = compile_chain(chain)
    ops = eng.op_steps()
    text = eng._fn(False).lower(*_specs(eng)).compile().as_text()
    insts = _entry(text)
    mapped = [i for i in insts if i in ops]
    assert len(mapped) >= 0.95 * len(insts), sorted(set(insts) - set(ops))
    assert set(ops.values()) <= {s.name for s in eng.steps}
    # XLA fuses elementwise steps into their neighbours' fusions, but each
    # conv and matmul step keeps instructions of its own
    assert {s.name for s in eng.steps
            if s.backend.startswith(("conv:", "matmul:"))} \
        <= set(ops.values())


def test_op_steps_reads_scopes_and_users():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[2]}",
        "",
        "%fused_computation (p: f32[2]) -> f32[2] {",
        '  %p = f32[2]{0} parameter(0)',
        '  ROOT %n = f32[2]{0} negate(%p), metadata={op_name="jit(f)/b/neg"}',
        "}",
        "",
        "ENTRY %main (x: f32[2]) -> f32[2] {",
        '  %x = f32[2]{0} parameter(0), metadata={op_name="x"}',
        "  %copy.1 = f32[2]{0} copy(%x)",
        '  %a.1 = f32[2]{0} sine(%copy.1), metadata={op_name="jit(f)/a/sin"}',
        '  %loose = f32[2]{0} cosine(%a.1), metadata={op_name="jit(f)/b"}',
        "  %fusion = f32[2]{0} fusion(%loose), kind=kLoop, "
        'calls=%fused_computation, metadata={op_name="jit(f)/b/neg"}',
        "  ROOT %tuple = (f32[2]{0}) tuple(%fusion)",
        "}",
    ])
    assert hlo_op_steps(text, ["a", "b"]) == {
        "copy.1": "a",       # no metadata: its user's step
        "a.1": "a",
        "loose": "b",        # "b" names the operation, not a scope: its
        "fusion": "b",       # user's step
        # %tuple lies outside every step; %x is a parameter; %p and %n
        # belong to a fusion body
    }


def test_named_scopes_leave_the_compiled_program_unchanged():
    chain, _inputs, _params = _case("GLN")
    eng = compile_chain(chain)
    outs = eng.chain.outputs or [list(eng.chain.nodes)[-1]]

    def bare(ins, ps):
        env = dict(ins)
        env.update(ps)
        for step in eng.steps:
            env[step.name] = step.run(env)
        return {o: env[o] for o in outs}

    def stripped(fn):
        text = jax.jit(fn).lower(*_specs(eng)).compile().as_text()
        # from the first computation on: the header and the tables of the
        # metadata's source lines go
        text = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
        return re.sub(r", metadata=\{[^}]*\}", "", text).splitlines()

    scoped = stripped(lambda ins, ps: eng._execute(ins, ps, False))
    assert scoped == stripped(bare)
    assert any(" fusion(" in ln for ln in scoped)


def test_build_counters_fire_once_per_program():
    chain, inputs, params = _case("GLN")
    eng = compile_chain(chain)

    def built():
        return eng.metrics.to_dict()["metrics"].get(
            "engine_programs_compiled", {"series": []})["series"]

    assert built() == []
    eng(inputs, params)
    assert built() == [{"labels": {"program": "exact"}, "value": 1.0}]
    assert eng.metrics.value("engine_trace_s", program="exact") > 0
    assert eng.metrics.value("engine_compile_s", program="exact") > 0
    eng(inputs, params)                      # found compiled: no build
    assert built() == [{"labels": {"program": "exact"}, "value": 1.0}]
    batch = {k: np.stack([v, v, v]) for k, v in inputs.items()}
    eng(batch, params)                       # bucket 4: one more program
    eng({k: v[:2] for k, v in batch.items()}, params)   # bucket 2
    eng(batch, params)
    assert {s["labels"]["program"]: s["value"] for s in built()} == {
        "exact": 1.0, "bucket=4": 1.0, "bucket=2": 1.0}
    assert eng.metrics.value("engine_compile_cache_hits",
                             program="bucket=4") == 0


def test_compile_chain_spans_its_phases():
    chain, _inputs, _params = _case("MN")
    eng = compile_chain(chain, profile=True, lint="error")
    assert [e["name"] for e in eng.tracer.events
            if e["type"] == "span" and e["cat"] == "compile"] == [
        "compile.partition", "compile.plan", "compile.lint"]


def test_nested_build_events_count_once():
    u = compiles._Union()
    u.add(2.0, 3.0)          # an inner trace ends first ...
    u.add(1.0, 5.0)          # ... inside the outer one
    assert u.total == 4.0
    u.add(7.0, 8.0)
    u.add(5.5, 6.0)          # out of order, disjoint
    assert u.total == 5.5
    u.add(0.0, 10.0)
    assert u.total == 10.0


def test_build_totals_count_programs_and_seconds():
    b = compiles.Builds()
    before = b.totals
    b._span(compiles.TRACE_EVENTS[0], 1.0, 1.5)
    b._span(compiles.TRACE_EVENTS[1], 1.5, 1.75)
    b._event(compiles.CACHE_HIT_EVENT)
    b._span(compiles.COMPILE_EVENT, 2.0, 4.0)
    b._span("/jax/something/else", 0.0, 100.0)
    d = b.totals - before
    assert d == compiles.BuildTotals(programs=1, cache_hits=1,
                                     trace_s=0.75, compile_s=2.0)
    after = b.totals
    b._event("/jax/compilation_cache/cache_misses")
    assert b.totals is after
