"""The split of device time by the engine's scope names
(`chipbench.scopes`): instructions of the compiled round mapped to their
phase, and busy time given to the innermost operation's phase."""
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, scopes, trace

import tiny

MS = 1e6  # ns
DATA = pathlib.Path(__file__).parent / "data"


def _opcode(rest: str) -> str:
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest.split(", metadata=")[0])
    return m.group(1) if m else ""


@pytest.fixture(scope="module", params=sorted(tiny.KINDS))
def compiled_round(request):
    cell = tiny.cell(request.param)
    prog = harness.Program(cell["config"], cell["traffic"], 1, jnp.float32,
                           jax.devices()[:1])
    return prog.rnd.as_text()


def test_every_kernel_of_the_round_maps_to_one_phase(compiled_round):
    """Every fusion, dot, while and custom-call of the tiny compiled
    round has a phase, and no `op_name` path names two (the phases do
    not nest)."""
    m = scopes.op_scopes(compiled_round)
    kernels = [name for _, name, _, rest in scopes.instructions(compiled_round)
               if _opcode(rest) in ("fusion", "dot", "while", "custom-call")]
    assert kernels
    assert {name: m[name][0] in scopes.PHASES for name in kernels} \
        == dict.fromkeys(kernels, True)
    for path in re.findall(r'op_name="([^"]*)"', compiled_round):
        for p in path.split(";"):
            assert sum(t in scopes.PHASES for t in re.split(r"[/()]", p)) <= 1, p


def test_loss_grad_runs_in_the_exchange_and_the_local_steps(compiled_round):
    m = scopes.op_scopes(compiled_round)
    assert {p for p, grad in m.values() if grad} \
        == {"exchange_corrections", "local_steps"}
    assert {p for p, _ in m.values()} >= set(scopes.PHASES)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(round)/local_steps/loss_grad/vmap(jvp())/while/body/dot_general",
     ("local_steps", True)),
    ("jit(round)/exchange_corrections/loss_grad/vmap(transpose(jvp()))/mul",
     ("exchange_corrections", True)),
    ("jit(round)/cos;jit(round)/aggregate/div", ("aggregate", False)),
    ("jit(round)/broadcast/broadcast_in_dim", ("broadcast", False)),
    ("jit(round)/cos", None),
    ("jit(round)/aggregate", None),  # the last component is the primitive
])
def test_path_scope_reads_the_first_path_naming_a_phase(op_name, scope):
    assert scopes.path_scope(op_name) == scope


@pytest.mark.parametrize("event,name", [
    ("%fusion.33 = f32[4096,14336]{1,0:T(8,128)} fusion(f32[8,4096,14336]{2,1,0} "
     "%p), kind=kLoop", "fusion.33"),
    ("%while.1316 = (s32[], f32[2]) while((s32[], f32[2]) %t)", "while.1316"),
    ("copy-start.4", "copy-start.4"),
])
def test_op_name_parses_an_xla_ops_event(event, name):
    assert scopes.op_name(event) == name


HLO = """HloModule jit_round, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exponential.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(round)/local_steps/loss_grad/vmap(jvp())/exp"}
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.3 = f32[4]{0} get-tuple-element(%p), index=1
  %copy.4 = f32[4]{0} copy(%gte.3)
  %gte.5 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.6 = (s32[], f32[4]{0}) tuple(%gte.5, %copy.4)
}

%cond.7 (q: (s32[], f32[4])) -> pred[] {
  %q = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.8 = pred[] constant(false)
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> (f32[4], s32[4]) {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %copy-start.10 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.11 = f32[4]{0} copy-done(%copy-start.10)
  %fusion.12 = f32[4]{0} fusion(%copy-done.11), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round)/broadcast/broadcast_in_dim"}
  %constant.13 = s32[] constant(0)
  %tuple.14 = (s32[], f32[4]{0}) tuple(%constant.13, %fusion.12)
  %while.15 = (s32[], f32[4]{0}) while(%tuple.14), condition=%cond.7, body=%body.2, metadata={op_name="jit(round)/exchange_corrections/loss_grad/while"}
  %gte.16 = f32[4]{0} get-tuple-element(%while.15), index=1
  %cosine.17 = f32[4]{0} cosine(%Arg_0.1), metadata={op_name="jit(round)/cos"}
  %multiply.18 = f32[4]{0} multiply(%gte.16, %cosine.17), metadata={op_name="jit(round)/aggregate/mul;jit(round)/broadcast/mul"}
  %iota.19 = s32[4]{0} iota(), iota_dimension=0
  ROOT %tuple.20 = (f32[4]{0}, s32[4]{0}) tuple(%multiply.18, %iota.19)
}
"""


def test_op_scopes_fill_in_ops_without_a_phase():
    m = scopes.op_scopes(HLO)
    lg_local, lg_exch = ("local_steps", True), ("exchange_corrections", True)
    assert m["fusion.12"] == lg_local  # its fused root, before its own
    assert m["copy-start.10"] == m["copy-done.11"] == lg_local  # from users
    assert m["while.15"] == lg_exch
    assert m["copy.4"] == m["gte.3"] == lg_exch  # from the loop
    assert m["multiply.18"] == ("aggregate", False)  # the first path
    assert m["cosine.17"] == ("aggregate", False)  # a hoisted invariant
    assert m["gte.16"] == ("aggregate", False)
    assert m["iota.19"] == (None, False)  # nothing around it names one


def _synthetic():
    """Two rounds of 10 ms; device 0 runs a loop whose body holds ops of
    two scopes, an op that overlaps the loop's end, two ops that start
    together, an op missing from the map and one outside the window;
    device 1 is busy with one op for the whole window."""
    host = [("round", 0, 10 * MS), ("dispatch", 0, 1 * MS),
            ("round", 10 * MS, 10 * MS)]
    dev0 = [("%while.1 = (s32[]) while(%t)", 1 * MS, 8 * MS),
            ("%fusion.2 = f32[8] fusion(%a)", 2 * MS, 2 * MS),
            ("fusion.3", 5 * MS, 2 * MS),
            ("fusion.4", 8 * MS, 4 * MS),      # overlaps while.1's end
            ("fusion.5", 13 * MS, 4 * MS),
            ("fusion.6", 13 * MS, 2 * MS),     # same start: the shorter
            ("copy.7", 17 * MS, 1 * MS),       # not in the map
            ("fusion.8", 25 * MS, 1 * MS)]     # outside the window
    dev1 = [("fusion.2", 0, 20 * MS)]
    m = {"while.1": ("exchange_corrections", True),
         "fusion.2": ("exchange_corrections", True),
         "fusion.3": ("aggregate", False),
         "fusion.4": ("local_steps", True),
         "fusion.5": ("broadcast", False),
         "fusion.6": ("aggregate", False),
         "fusion.8": ("broadcast", False)}
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}, m


def test_reduce_gives_each_instant_to_the_innermost_op():
    ev, m = _synthetic()
    r = scopes.reduce(ev, m)
    # device 0 (ms): while 1+1+1 and fusion.2 2 -> exchange 5; fusion.3 2
    # + fusion.6 2 -> aggregate 4; fusion.4 [8, 12) -> local 4; fusion.5
    # [15, 17) -> broadcast 2; copy.7 -> unscoped 1; busy 16, loss_grad 9.
    # device 1: exchange 20.  Mean over 2 devices, per 2 rounds:
    ms = 1e-3 / 4
    assert r["rounds"] == 2
    assert r["exchange_corrections_s"] == pytest.approx(25 * ms)
    assert r["aggregate_s"] == pytest.approx(4 * ms)
    assert r["local_steps_s"] == pytest.approx(4 * ms)
    assert r["broadcast_s"] == pytest.approx(2 * ms)
    assert r["unscoped_s"] == pytest.approx(1 * ms)
    assert r["loss_grad_s"] == pytest.approx(29 * ms)
    assert r["busy_s"] == pytest.approx(36 * ms)
    assert r["unscoped_share"] == pytest.approx(1 / 36)
    parts = sum(r[k] for k in scopes.METRICS if k != "loss_grad_s")
    assert parts == pytest.approx(r["busy_s"], rel=1e-12)
    assert r["busy_s"] * r["rounds"] == pytest.approx(trace.reduce(ev)["busy_s"])


def test_reduce_needs_a_round_span_and_device_ops():
    ev, m = _synthetic()
    with pytest.raises(ValueError):
        scopes.reduce({"devices": ev["devices"], "host": [("run", 0, 1)]}, m)
    with pytest.raises(ValueError):
        scopes.reduce({"devices": {}, "host": ev["host"]}, m)


def test_a_recorded_v5e_trace_with_an_empty_map_is_all_unscoped():
    """`data/v5e_tiny.xplane.pb` (three rounds recorded on a v5e): with
    no scope map every busy instant is unscoped, and `trace.reduce`
    reads the same events as before."""
    ev = trace.load(str(DATA / "v5e_tiny.xplane.pb"))
    before = trace.reduce(ev, host_files=())
    r = scopes.reduce(ev, {})
    assert r["unscoped_share"] == 1.0 and r["named_ops"] == 0
    assert r["rounds"] == 3
    assert r["busy_s"] * 3 == pytest.approx(before["busy_s"])
    assert all(r[f"{p}_s"] == 0.0 for p in scopes.PHASES)
    assert trace.reduce(ev, host_files=()) == before
    assert before["busy_s"] == pytest.approx(3.139e-06)
