"""The scope reader (``harness.scopes``) and the arithmetic of the metrics
that read the block programs' scopes, round counters and compiles."""

import os

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness import scopes, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# not ``*.xplane.pb``: test_trace.py reads the newest such file in DATA
RECORDED = os.path.join(DATA, "scopes.trace.pb")
WS_PATH = "jit(<lambda>)/vmap(jit(dt_watershed))/"


def test_phase_of():
    assert scopes.phase_of(WS_PATH + "ws.flood/while:", "ws.") == "ws.flood"
    assert scopes.phase_of("jit(<lambda>)/vmap(ws.reclose)/slice:",
                           "ws.") == "ws.reclose"
    seeds = WS_PATH + "ws.seeds/jit(connected_components)/cc.rank/iota:"
    assert scopes.phase_of(seeds, "ws.") == "ws.seeds"
    assert scopes.phase_of(seeds, "cc.") == "cc.rank"
    assert scopes.phase_of("jit(f)/news.flood/x:", "ws.") == scopes.UNSCOPED
    assert scopes.phase_of("", "ws.") == scopes.UNSCOPED


def made_up():
    """One device: two runs of ``jit__lambda`` ([0, 100) and [200, 260))
    and one of another program; a ``while`` in ``ws.flood`` with body
    operations nested in it, a ``while`` with no name of its own
    whose body is in ``ws.size_filter``, and an unnamed operation between
    two of ``ws.flood``."""
    return {0: scopes.DeviceOps(
        modules=[("jit__lambda(7)", 0.0, 100.0),
                 ("jit_other(8)", 120.0, 40.0),
                 ("jit__lambda(7)", 200.0, 60.0)],
        ops=[(5.0, 10.0, WS_PATH + "ws.dt/fusion:"),
             (20.0, 50.0, WS_PATH + "ws.flood/while:"),
             (25.0, 10.0, "ws.flood/while/body/fusion:"),
             (40.0, 5.0, "while/body/reduce:"),  # nested, no path of its own
             (72.0, 3.0, ""),  # unnamed, between two of ws.flood
             (76.0, 2.0, WS_PATH + "ws.flood/fusion:"),
             (80.0, 10.0, ""),  # no scope, and none after it
             (130.0, 20.0, WS_PATH + "ws.seeds/x:"),  # another program
             (200.0, 8.0, ""),  # a while the compiler left unnamed
             (201.0, 5.0, "ws.size_filter/while/body/fusion:"),
             (206.0, 1.0, "while/cond/compare:"),
             (210.0, 40.0, WS_PATH + "ws.seeds/fusion:")],
    )}


def test_phase_ns_outermost_operation_wins():
    got = scopes.phase_ns(made_up(), scopes.WS_PROGRAM, "ws.", (0.0, 1e3))
    assert got == {"ws.dt": 10.0, "ws.flood": 55.0, scopes.UNSCOPED: 10.0,
                   "ws.size_filter": 8.0, "ws.seeds": 40.0}
    rules = [t[2] for t in scopes.outermost(
        made_up(), scopes.WS_PROGRAM, "ws.", (0.0, 1e3))]
    assert rules == ["own", "own", "between", "own", "none", "nested",
                     "own"]
    # the phases add up to the union of the program's operations
    ops = [(s, s + d) for s, d, _ in made_up()[0].ops if s < 100 or s >= 200]
    assert sum(got.values()) == sum(e - s for s, e in xtrace.union(ops))


def test_phase_ns_clips_to_the_window():
    got = scopes.phase_ns(made_up(), scopes.WS_PROGRAM, "ws.", (30.0, 220.0))
    assert got == {"ws.flood": 45.0, scopes.UNSCOPED: 10.0,
                   "ws.size_filter": 8.0, "ws.seeds": 10.0}
    assert scopes.phase_ns(made_up(), ("jit_none",), "ws.", (0, 1e3)) == {}


def _xplane(devices):
    """Serialized XSpace of ``made_up``-style devices (one line each of
    modules and operations, ``timestamp_ns`` 0)."""
    space = scopes._xspace_class()()
    for dev, d in devices.items():
        plane = space.planes.add(name=f"/device:TPU:{dev}")
        plane.stat_metadata.add(key=1).value.name = "tf_op"
        ids = {}

        def meta(name, path=None):
            key = (name, path)
            if key not in ids:
                ids[key] = len(ids) + 1
                m = plane.event_metadata.add(key=ids[key]).value
                m.id, m.name = ids[key], name
                if path is not None:
                    m.stats.add(metadata_id=1, str_value=path)
            return ids[key]

        mods = plane.lines.add(name=xtrace.MODULES, timestamp_ns=0)
        for name, s, dur in d.modules:
            mods.events.add(metadata_id=meta(name), offset_ps=int(s * 1e3),
                            duration_ps=int(dur * 1e3))
        ops = plane.lines.add(name=xtrace.OPS, timestamp_ns=0)
        for i, (s, dur, path) in enumerate(d.ops):
            ops.events.add(metadata_id=meta(f"op{i}", path),
                           offset_ps=int(s * 1e3), duration_ps=int(dur * 1e3))
    return space.SerializeToString()


def test_decode_round_trip():
    assert scopes.decode(_xplane(made_up())) == made_up()


def scoped_context(tmp_path, devices=None):
    cell = cell_mod.Cell(name="x.ws", chips=1,
                         config={"block_shape": [10, 100, 100],
                                 "volume_shape": [20, 200, 200]},
                         traffic={})
    ctx = cell_mod.Context(cell, output_path=str(tmp_path / "output.n5"))
    ctx.jobs = [{"index": 0, "t0": 0.0, "t1": 10.0, "begin": (0, 0, 0),
                 "end": (10, 100, 100)},
                {"index": 1, "t0": 10.0, "t1": 20.0, "begin": (0, 0, 100),
                 "end": (10, 100, 200)}]  # 0.2 Mvox
    ctx.trace = xtrace.DeviceTrace(window=(0.0, 1e3))
    run = tmp_path / "profile" / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(_xplane(devices or made_up()))
    return ctx


def read(name, ctx):
    return cell_mod.load_module("metrics", name).read(ctx)


def test_phase_metrics_per_mvox(tmp_path):
    ctx = scoped_context(tmp_path)
    # ns -> ms over 0.2 Mvox
    assert read("ws_program.flood_ms_per_mvox", ctx) == pytest.approx(
        55e-6 / 0.2)
    assert read("ws_program.seeds_ms_per_mvox", ctx) == pytest.approx(
        40e-6 / 0.2)
    assert read("ws_program.unscoped_ms_per_mvox", ctx) == pytest.approx(
        10e-6 / 0.2)
    # a phase that did not run reads 0, a program that did not run nothing
    assert read("ws_program.reclose_ms_per_mvox", ctx) == 0.0
    assert read("cc_program.tiles_ms_per_mvox", ctx) is None
    ctx.trace = None
    assert read("ws_program.flood_ms_per_mvox", ctx) is None


def test_phase_metrics_silent_without_scopes(tmp_path):
    """A program without named scopes (the parent's) reads nothing."""
    bare = {0: scopes.DeviceOps(
        modules=[("jit__lambda(7)", 0.0, 100.0)],
        ops=[(5.0, 10.0, "jit(<lambda>)/vmap(jit(dt_watershed))/while:")])}
    ctx = scoped_context(tmp_path, bare)
    for phase in ("dt", "flood", "unscoped"):
        assert read(f"ws_program.{phase}_ms_per_mvox", ctx) is None


def counters_context(**counters):
    cell = cell_mod.Cell(name="x.ws", chips=1, config={
        "block_shape": [1, 1, 1], "volume_shape": [1, 1, 1]}, traffic={})
    ctx = cell_mod.Context(cell)
    ctx.jobs = [{"t0": 0.0, "t1": 30.0}, {"t0": 30.0, "t1": 50.0}]
    ctx.counters = dict(counters)
    return ctx


def test_round_and_compile_metrics():
    ctx = counters_context(**{"blocks.computed": 4.0, "flood.rounds": 120.0,
                              "cc.rounds": 8.0, "jit.compile_s": 1.0})
    assert read("flood.rounds_per_block", ctx) == pytest.approx(30.0)
    assert read("cc.rounds_per_block", ctx) == pytest.approx(2.0)
    assert read("jit.compile_share", ctx) == pytest.approx(2.0)
    ctx.counters["jit.compile_s"] = 0.0  # no compile in the window
    assert read("jit.compile_share", ctx) == 0.0


def test_round_and_compile_metrics_silent_without_counters():
    ctx = counters_context(**{"store.bytes_read": 1.0})
    for name in ("flood.rounds_per_block", "cc.rounds_per_block",
                 "jit.compile_share"):
        assert read(name, ctx) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_by_scope():
    """A trace recorded on a v5e by ``record_scopes.py``: three runs of a
    program with a matmul in ``demo.matmul``, a loop in ``demo.loop`` and
    a cumsum outside them."""
    devices = scopes.decode(open(RECORDED, "rb").read())
    assert list(devices) == [0]
    d = devices[0]
    mods = [m for m in d.modules if m[0].startswith("jit_scoped(")]
    assert len(mods) == 3
    got = scopes.phase_ns(devices, ("jit_scoped",), "demo.", (0.0, 1e18))
    assert set(got) == {"demo.matmul", "demo.loop", scopes.UNSCOPED}
    assert all(v > 0 for v in got.values())
    inside = [(s, s + dur) for s, dur, _ in d.ops
              if any(m0 <= s < m0 + md for _, m0, md in mods)]
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in xtrace.union(inside)))
    # the loop's ``while`` carries no name; its body and condition do, so
    # its time is the loop's and only the cumsum is left unscoped
    loops = [dur for s, dur, path in d.ops
             if not path and dur > got["demo.matmul"] / 3 / 2]
    assert len(loops) == 3
    assert got["demo.loop"] >= sum(loops)
    assert got[scopes.UNSCOPED] < got["demo.loop"] / 4


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_on_the_xtrace_clock(tmp_path):
    """The decoded times are those ``jax.profiler.ProfileData`` gives, to
    the nanosecond it rounds to."""
    (tmp_path / "t").mkdir()
    os.symlink(RECORDED, tmp_path / "t" / "scopes.xplane.pb")
    tr = xtrace.load(str(tmp_path / "t"))
    d = scopes.decode(open(RECORDED, "rb").read())[0]
    assert len(d.ops) == len(tr.ops[0])
    for k in (0, 1):  # starts, then durations
        ours = sorted(op[k] for op in d.ops)
        theirs = sorted(op[k + 1] for op in tr.ops[0])
        assert max(abs(a - b) for a, b in zip(ours, theirs)) < 1.0


def test_traced_rehearsal_reads_the_program_counters(rehearsal, capsys,
                                                     monkeypatch, tmp_path):
    """A traced run of a tiny ws cell on the CPU reads the rounds the
    program returned and the compiles of the window; the device readers
    find no TPU plane there and stay silent."""
    import json

    import jax
    from conftest import DATA, last_json

    jax.clear_caches()  # so this process compiles (or loads) its programs

    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in ("flood.rounds_per_block", "cc.rounds_per_block",
                 "jit.compile_share", "ws_program.flood_ms_per_mvox"):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "program_counter", "layer": "kernels",
            "moves": "mvox_s"})
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    monkeypatch.setattr(rehearsal, "BENCH_FILE", str(bench_file))
    assert rehearsal.main(["--workload", "tiny.ws", "--seed", "2147483700",
                           "--seconds", "0.05", "--trace", "1"]) == 0
    result = last_json(capsys.readouterr().out)
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["flood.rounds_per_block"] > 0
    assert got["cc.rounds_per_block"] > 0
    assert got["jit.compile_share"] >= 0
    assert "ws_program.flood_ms_per_mvox" not in got
