"""Device time per outer iteration by stage of the algorithm, from the trace.

The program names the stages of an outer iteration with ``jax.named_scope``:
``sodda.issue`` (the sample draw and the snapshot gradient's passes over X),
``sodda.exchange`` (the mesh step's collectives), ``sodda.consume`` (the row
gather and re-layout, the inner chains, the assembly) and
``sodda.objective`` (the recorded F(w)). A scope lands in the ``op_name``
metadata of every HLO instruction traced under it, and survives into the
optimized program; the trace names its device ops by instruction
(``bench/trace.py``'s ``op_s``). So :func:`split` joins the two: it
rebuilds the cell's program exactly as ``bench/run.py`` calls it, lowers it
with the cell's shapes and shardings, compiles it (the compilation cache
keyed on metadata too: see :func:`_program_stages`), maps each instruction to
the innermost ``sodda.*`` component of its ``op_name`` (or to
``unscoped``), and sums the trace's self seconds by stage.

The names are copied here, not imported from the program: a scope renamed
in the program then labels nothing, and its metric reads nothing, instead
of its time moving quietly to ``unscoped``.

Limits. The trace holds every program that ran in the window, and names an
op by its instruction's name alone: the harness's own ``fit.init``
programs (the initial state and its placement, microseconds a fit) run
there too, and a name of theirs that is also an instruction of the fit
program is counted as that instruction. Names the fit program does not
have count against coverage; under 99 % of the traced seconds, the lowering
did not match what ran, and every stage reads nothing.
"""
from __future__ import annotations

import functools
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

SCOPES = {"issue": "sodda.issue", "exchange": "sodda.exchange",
          "consume": "sodda.consume", "objective": "sodda.objective"}
UNSCOPED = "unscoped"
COVERAGE = 0.99

_SCOPE_RE = re.compile(r"(?<![\w.])(%s)(?![\w.])" % "|".join(
    re.escape(s) for s in SCOPES.values()))
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = {name: stage for stage, name in SCOPES.items()}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def scope_of(op_name: str) -> str:
    """The stage of an instruction whose ``op_name`` is `op_name`: its
    innermost ``sodda.*`` scope, or ``unscoped``."""
    found = _SCOPE_RE.findall(op_name)
    return _STAGE[found[-1]] if found else UNSCOPED


def instruction_stages(hlo_text: str) -> Dict[str, str]:
    """Every instruction of an HLO module's text, by name, to its stage.

    An instruction with an ``op_name`` takes its innermost scope. One with
    none was made by a compiler pass, and takes the stage of the
    instruction that calls its computation: XLA expands the consume half's
    batched gather into a while loop whose body carries no metadata, but
    whose ``while`` carries the gather's.
    """
    own, home, caller = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            h = _COMPUTATION.match(line)
            if h:
                comp = h.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        home[name] = comp
        for callee in _callees(line):
            caller.setdefault(callee, name)

    stages: Dict[str, str] = {}

    def stage(name):
        seen = []
        while name not in stages and own[name] is None:
            seen.append(name)
            up = caller.get(home[name])
            if up is None or up in seen:
                break
            name = up
        got = stages.get(name) or own[name] or UNSCOPED
        for n in seen + [name]:
            stages[n] = got

    for name in own:
        stage(name)
    return stages


def _callees(line: str):
    """The computations an instruction's line calls."""
    out = _CALLED.findall(line)
    for group in _BRANCHES.findall(line):
        out += [c.strip().lstrip("%") for c in group.split(",")]
    return out


def split(op_s: Dict[str, float], stages: Dict[str, str], chips: int,
          iters: int) -> Optional[Dict[str, Optional[float]]]:
    """Milliseconds of device time per outer iteration and chip, by stage.

    `op_s` is the trace's self seconds per instruction name, summed over
    `chips`; `stages` maps the fit program's instructions to stages;
    `iters` is the number of outer iterations in the window. A stage whose
    scope labels no instruction of the program reads None; nothing at all
    (None) when no scope labels any, or when the program's names cover
    less than :data:`COVERAGE` of the traced seconds.
    """
    present = set(stages.values())
    if not present & set(SCOPES):
        _log("scopes: the program holds no sodda.* scope")
        return None
    total = sum(op_s.values())
    seconds = dict.fromkeys(list(SCOPES) + [UNSCOPED], 0.0)
    for name, s in op_s.items():
        if name in stages:
            seconds[stages[name]] += s
    covered = sum(seconds.values())
    if total <= 0 or covered < COVERAGE * total:
        missing = sorted(((s, n) for n, s in op_s.items()
                          if n not in stages), reverse=True)[:5]
        _log(f"scopes: the program's instructions cover {covered:.6f} s of "
             f"{total:.6f} s traced, under {COVERAGE:.0%}; largest unknown "
             f"ops {missing}: no stage is read")
        return None
    scale = 1e3 / chips / iters
    return {stage: (s * scale if stage in present or stage == UNSCOPED
                    else None)
            for stage, s in seconds.items()}


def _program_stages(config_json: str, fit_iters: int,
                    record_every: int) -> Dict[str, str]:
    """Stages of the instructions of the fit program ``bench/run.py``
    runs for this configuration and traffic, compiled again from its shapes
    and shardings (X itself is not made twice)."""
    import jax
    import jax.numpy as jnp

    from bench import spec
    from repro.core import distributed, driver, engine, sodda

    config = json.loads(config_json)
    cfg = spec.sodda_config(config)
    backend = config["backend"]
    mesh = (engine.make_mesh_for(cfg) if backend in engine.MESH_BACKENDS
            else None)
    run = driver.make_run(cfg, fit_iters, backend,
                          record_every=record_every, mesh=mesh)
    state = driver.place_initial_state(
        sodda.init_state(jax.random.PRNGKey(0), cfg.M), cfg, backend, mesh)
    if mesh is None:
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        x_sh, y_sh = one, one
    else:
        x_sh, y_sh = distributed.data_shardings(mesh)
    X = jax.ShapeDtypeStruct((cfg.N, cfg.M), jnp.float32, sharding=x_sh)
    y = jax.ShapeDtypeStruct((cfg.N,), jnp.float32, sharding=y_sh)
    # The compilation cache leaves metadata out of its key, so a cache
    # shared with another build of the same instructions (the parent
    # commit's, say) hands back that build's executable and op_names. Keyed
    # on metadata too, and past the in-memory caches, this compile finds
    # only an executable of this program's own text: a real compile the
    # first time in a cache, a hit after.
    option = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, option)
    jax.clear_caches()
    jax.config.update(option, True)
    t = time.perf_counter()
    try:
        text = run.lower(state, X, y).compile().as_text()
    finally:
        jax.config.update(option, was)
    _log(f"scopes: the fit program compiled again in "
         f"{time.perf_counter() - t:.3f} s")
    return instruction_stages(text)


@functools.lru_cache(maxsize=8)
def _stages_or_none(config_json: str, fit_iters: int,
                    record_every: int) -> Optional[Dict[str, str]]:
    try:
        return _program_stages(config_json, fit_iters, record_every)
    except Exception:  # a reader reads nothing; it never fails a run
        _log("scopes: the fit program could not be compiled again:\n"
             + traceback.format_exc())
        return None


def per_iteration_ms(ctx: dict) -> Optional[Dict[str, Optional[float]]]:
    """:func:`split` of a traced run's context, the program compiled once
    per process for all of its readers."""
    traffic = ctx["traffic"]
    stages = _stages_or_none(json.dumps(ctx["config"], sort_keys=True),
                             int(traffic["fit_iters"]),
                             int(traffic["record_every"]))
    iters = ctx["fits"] * int(traffic["fit_iters"])
    if stages is None or not iters:
        return None
    return split(ctx["trace"]["op_s"], stages, ctx["chips"], iters)


def read(ctx: dict, stage: str) -> Optional[float]:
    """One stage's milliseconds per iteration, or None."""
    got = per_iteration_ms(ctx)
    return None if got is None else got[stage]
