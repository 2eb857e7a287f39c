"""The stages of an outer iteration are named in every compiled run.

``repro.core.sodda`` names four ``jax.named_scope``s: issue, exchange,
consume and objective. They are metadata only, written into the ``op_name``
of each HLO instruction traced under them, and a profile splits device time
by stage through them. Here every registered engine backend's
``driver.make_run`` program is compiled at test size and read back: each
stage the backend runs labels at least one instruction of a non-fused
computation (the ops a device trace names), as the innermost scope.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import driver, engine, sodda
from repro.core.distributed import data_shardings
from repro.testing import small_fixture_config, sodda_test_mesh

SCOPES = {"issue": sodda.ISSUE_SCOPE, "exchange": sodda.EXCHANGE_SCOPE,
          "consume": sodda.CONSUME_SCOPE, "objective": sodda.OBJECTIVE_SCOPE}
STEP = {"issue", "consume", "objective"}
EXPECTED = {
    "reference": STEP,
    "pallas": STEP,
    "async": STEP,
    "shard_map": STEP | {"exchange"},
    "shard_map+pallas": STEP | {"exchange"},
    "async-mesh": STEP | {"exchange"},
    # RADiSA-avg runs none of SODDA's step helpers: only the driver's
    # recorded objective is named in its program
    "radisa-avg": {"objective"},
}
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+\S+\s+([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?<![\w.])(%s)(?![\w.])" % "|".join(
    re.escape(s) for s in SCOPES.values()))


def _stage(op_name):
    found = _SCOPE.findall(op_name)
    return {v: k for k, v in SCOPES.items()}[found[-1]] if found else None


def _unfused(text):
    """(name, opcode, op_name, stage) of each instruction of a non-fused
    computation of an HLO module's text."""
    fused, rows, comp = set(), [], None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and " fusion(" in line:
            fused.update(_CALLS.findall(line))
        if m and comp is not None:
            op = _OP_NAME.search(line)
            op = op.group(1) if op else ""
            rows.append((comp, m.group(1), m.group(2), op, _stage(op)))
        elif not line.startswith(" "):
            h = _HEADER.match(line)
            comp = h.group(1) if h else None
    return [r[1:] for r in rows if r[0] not in fused]


@pytest.fixture(scope="module")
def programs():
    """The optimized HLO text of each backend's 3-iteration run program.

    The compilation cache leaves metadata out of its key: an executable
    that another build of the same instructions wrote there would bring
    that build's op_names. So the compiles here are keyed on metadata too,
    and start past the in-memory caches."""
    cfg = small_fixture_config()
    mesh = sodda_test_mesh(cfg)
    cache = {}
    option = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, option)
    jax.clear_caches()
    jax.config.update(option, True)

    def get(backend):
        if backend not in cache:
            m = mesh if backend in engine.MESH_BACKENDS else None
            run = driver.make_run(cfg, 3, backend, record_every=2, mesh=m)
            state = driver.place_initial_state(
                sodda.init_state(jax.random.PRNGKey(0), cfg.M), cfg,
                backend, m)
            if m is None:
                x_sh = y_sh = jax.sharding.SingleDeviceSharding(
                    jax.devices()[0])
            else:
                x_sh, y_sh = data_shardings(m)
            X = jax.ShapeDtypeStruct((cfg.N, cfg.M), jnp.float32,
                                     sharding=x_sh)
            y = jax.ShapeDtypeStruct((cfg.N,), jnp.float32, sharding=y_sh)
            cache[backend] = _unfused(
                run.lower(state, X, y).compile().as_text())
        return cache[backend]

    yield get
    jax.config.update(option, was)


def test_every_backend_is_named():
    assert set(EXPECTED) == set(engine.available_backends())


@pytest.mark.parametrize("backend", sorted(EXPECTED))
def test_each_stage_labels_the_program(programs, backend):
    rows = programs(backend)
    labelled = {stage for *_, stage in rows} - {None}
    assert labelled == EXPECTED[backend], labelled
    if backend in ("pallas", "shard_map+pallas"):
        # the inner kernel (interpreted here: the ops of its jit) is consume
        kernel = {stage for _, _, op, stage in rows
                  if "/sodda_inner/" in op}
        assert kernel == {"consume"}, kernel
    if backend in engine.MESH_BACKENDS:
        colls = [(name, stage) for name, code, _, stage in rows
                 if code.startswith(COLLECTIVES)]
        # every collective of the step is the exchange's; the objective's
        # own reductions stay the objective's
        assert {s for _, s in colls} == {"exchange", "objective"}, colls


def test_innermost_scope_names_the_stage():
    assert _stage("jit(_run)/while/body/shard_map/sodda.issue/"
                  "sodda.exchange/psum") == "exchange"
    assert _stage("jit(_run)/sodda.objective/dot_general") == "objective"
    assert _stage("jit(_run)/while/body/dynamic_update_slice") is None
    assert _stage("jit(f)/sodda.issued/add") is None
