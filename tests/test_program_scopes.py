"""The block programs name their phases and count their rounds, and
tracing changes nothing that is compiled.

The fused DT-watershed program (``tasks/watershed.py``) and the block
components program (``tasks/thresholded_components.py``) are lowered at a
test's size: every operation's location path holds exactly one of the
program's phase scopes (constants, which run nothing, may hold none), no
host callback is in the program, and the StableHLO without debug
information is the same with tracing off and on.  The round counts they
return equal those of ``flood_with_stats`` and of the flat CC kernel on
the same input.  A device trace reads these paths back
(``benchmark/harness/scopes.py``); nothing in the package lowers,
compiles or fingerprints a program to map them.
"""

import ast
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cluster_tools_tpu.obs import metrics, trace
from cluster_tools_tpu.ops import _backend

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "cluster_tools_tpu")
BLOCK = (8, 32, 32)
WS_PHASES = ("ws.dt", "ws.seeds", "ws.hmap", "ws.flood", "ws.size_filter",
             "ws.reclose")
CC_PHASES = ("cc.threshold", "cc.tiles", "cc.merge", "cc.flat", "cc.rank")


@pytest.fixture
def traced(tmp_path):
    metrics.reset()
    run_id = trace.enable(str(tmp_path / "trace"), "scopes", export_env=False)
    yield os.path.join(str(tmp_path / "trace"), run_id)
    trace.disable()
    metrics.reset()


def _ws_program():
    from cluster_tools_tpu.tasks.watershed import WatershedTask, _fused_ws_kernel

    params = WatershedTask._kernel_params(WatershedTask.default_task_config())
    # crop_cc: the re-close of a halo'd block is in the program too
    fn = _fused_ws_kernel(tuple(sorted(params.items())), BLOCK, False, True,
                          None)
    args = (jax.ShapeDtypeStruct((1,) + BLOCK, jnp.float32),
            jax.ShapeDtypeStruct((1,) + BLOCK, jnp.bool_),
            jax.ShapeDtypeStruct((1, 3), jnp.int32))
    return fn, args


def _cc_program():
    from cluster_tools_tpu.tasks.thresholded_components import (
        _components_batch,
    )

    def fn(batch, threshold):
        return _components_batch(batch, threshold, "greater", 0.0, 1)

    return jax.jit(fn), (jax.ShapeDtypeStruct((1,) + BLOCK, jnp.float32),
                         jax.ShapeDtypeStruct((), jnp.float32))


PROGRAMS = {"ws": (_ws_program, "ws."), "cc": (_cc_program, "cc.")}


def _lower(name):
    make, _ = PROGRAMS[name]
    fn, args = make()
    return fn.lower(*args)


# -- location paths of a lowered module -------------------------------------

_LOC_DEF = re.compile(r"^#(loc\d*) = loc\((.*)\)$", re.M)
_FUNC = re.compile(r"^\s*func\.func (?:public |private )?@([\w.$-]+)\(")
_TRAIL = re.compile(r'loc\((#loc\d*|"[^"]*")\)\s*$')
_CALL = re.compile(r"\bcall @([\w.$-]+)\(")


def _loc_names(text):
    """``#locN`` -> the name path it carries (None for a file location or
    an unknown one)."""
    defs = dict(_LOC_DEF.findall(text))

    def name(body):
        if body is None:
            return None
        m = re.match(r'^"((?:[^"\\]|\\.)*)"(.*)$', body)
        if m:
            return None if re.match(r"^:\d", m.group(2)) else m.group(1)
        m = re.match(r"^callsite\(#(loc\d*) at #loc\d*\)$", body)
        return name(defs.get(m.group(1))) if m else None

    return {ref: name(body) for ref, body in defs.items()}


def _functions(text):
    """function -> {"ops": [(line, name)], "calls": [(callee, name)]}: each
    line that ends in a location, the name of that location relative to
    its function (a name stack restarts in every lowered function)."""
    names = _loc_names(text)
    funcs, cur = {}, None
    for line in text.splitlines():
        m = _FUNC.match(line)
        if m:
            cur = funcs.setdefault(m.group(1), {"ops": [], "calls": []})
            continue
        if cur is None:
            continue
        if line.startswith(("  } loc", "}")):
            cur = None  # the function's own location
            continue
        t = _TRAIL.search(line)
        if not t:
            continue
        ref = t.group(1)
        nm = names.get(ref[1:]) if ref.startswith("#") else ref.strip('"')
        s = line.strip()
        call = _CALL.search(s)
        if call:
            cur["calls"].append((call.group(1), nm))
        elif not s.startswith(("return", "stablehlo.return", "func.return")):
            cur["ops"].append((s, nm))
    return funcs


def op_paths(text):
    """``(op line, full location path or None)`` of every operation of the
    lowered module, its path prefixed by the call sites from ``main``."""
    funcs = _functions(text)
    out = []

    def walk(fn, prefix):
        for s, nm in funcs[fn]["ops"]:
            out.append((s, None if nm is None
                        else "/".join(p for p in (prefix, nm) if p)))
        for callee, nm in funcs[fn]["calls"]:
            walk(callee, "/".join(p for p in (prefix, nm or "") if p))

    walk("main", "")
    return out


def phases_in(path, prefix):
    return set(re.findall(r"(?:^|[/(])(%s[a-z_]+)(?=[)/:]|$)"
                          % re.escape(prefix), path))


@pytest.mark.parametrize("cc_mode", ["flat", "coarse"])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_op_holds_one_phase_scope(program, cc_mode):
    prefix = PROGRAMS[program][1]
    known = set(WS_PHASES if program == "ws" else CC_PHASES)
    with _backend.force_cc_mode(cc_mode):
        text = _lower(program).as_text(debug_info=True)
    paths = op_paths(text)
    assert len(paths) > 100
    seen, bad = set(), []
    for s, path in paths:
        got = phases_in(path or "", prefix)
        seen |= got
        constant = "stablehlo.constant" in s
        if len(got) != 1 and not (constant and not got):
            bad.append((path, s[:80]))
    assert not bad, bad[:10]
    assert seen <= known, seen - known
    if program == "ws":
        assert seen == known
    else:
        assert {"cc.threshold", "cc.rank"} <= seen
        assert ("cc.flat" in seen) == (cc_mode == "flat")
        assert ({"cc.tiles", "cc.merge"} <= seen) == (cc_mode == "coarse")


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_no_host_callback_in_the_program(program):
    text = _lower(program).as_text()
    targets = re.findall(r'custom_call[^\n]*?@([\w.$-]+)', text)
    targets += re.findall(r'call_target_name = "([^"]+)"', text)
    assert not [t for t in targets if t.lower().endswith("callback")]
    assert "callback" not in text.lower()


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_tracing_changes_nothing_compiled(program, traced):
    from cluster_tools_tpu.tasks.watershed import _fused_ws_kernel

    def fresh():
        jax.clear_caches()
        _fused_ws_kernel.cache_clear()
        return _lower(program).as_text()

    on = fresh()
    trace.disable()
    off = fresh()
    assert off == on


def _blob(seed=3):
    from scipy import ndimage

    rng = np.random.default_rng(seed)
    x = ndimage.gaussian_filter(rng.random(BLOCK), (0.5, 2.0, 2.0))
    return ((x - x.min()) / (x.max() - x.min())).astype(np.float32)


def test_ws_rounds_equal_flood_with_stats():
    """The fused program's flood rounds are those of ``flood_with_stats``
    on the flood's own input, and of the size filter's re-flood."""
    from cluster_tools_tpu.ops.dt import distance_transform_2d_stack
    from cluster_tools_tpu.ops.watershed import (
        dt_seeds, flood_with_stats, make_hmap,
    )
    from cluster_tools_tpu.tasks.watershed import (
        WatershedTask, _fused_ws_kernel,
    )

    conf = WatershedTask.default_task_config()
    params = WatershedTask._kernel_params(conf)
    x = _blob()
    fn = _fused_ws_kernel(tuple(sorted(params.items())), BLOCK, False, False,
                          None)
    labels, rounds = jax.device_get(fn(x[None], np.ones((1,) + BLOCK, bool),
                                       np.zeros((1, 3), np.int32)))
    assert set(rounds) == {"flood", "flood_tile", "cc"}
    assert rounds["flood_tile"] == []  # no tile warm start by default
    assert [r.shape for r in rounds["flood"]] == [(1,), (1,)]

    fg = jnp.asarray(x < params["threshold"])
    dt = distance_transform_2d_stack(fg, pixel_pitch=None)
    seeds, _ = dt_seeds(dt, params["sigma_seeds"], per_slice=True)
    hmap = make_hmap(jnp.asarray(x), dt, params["alpha"],
                     params["sigma_weights"], per_slice=True)
    lab, _, stats = flood_with_stats(hmap, seeds, fg, per_slice=True)
    want = int(stats["flood_alt_iters"] + stats["flood_assign_iters"])
    assert int(rounds["flood"][0][0]) == want > 0
    # the size filter's re-flood from the kept segments
    lab = np.asarray(lab)
    n = int(np.prod(BLOCK)) // 2 + 2
    counts = np.bincount(lab.reshape(-1), minlength=n)
    kept = np.where(counts[lab] < params["size_filter"], 0, lab)
    final, _, stats = flood_with_stats(hmap, jnp.asarray(kept), fg,
                                       per_slice=True)
    want = int(stats["flood_alt_iters"] + stats["flood_assign_iters"])
    assert int(rounds["flood"][1][0]) == want > 0
    np.testing.assert_array_equal(labels[0], np.asarray(final))
    assert int(rounds["cc"][0][0]) > 0


def test_cc_rounds_equal_the_flat_kernel():
    from cluster_tools_tpu.ops.cc import connected_components_raw_with_iters
    from cluster_tools_tpu.tasks.thresholded_components import (
        _components_batch,
    )

    x = _blob(5)
    with _backend.force_cc_mode("flat"):
        _, _, rounds = _components_batch(x[None], 0.5, "greater", 0.0, 1)
        _, iters = connected_components_raw_with_iters(jnp.asarray(x > 0.5))
    assert int(rounds[0]) == int(iters) > 0


def test_count_block_rounds_counts_real_blocks(traced):
    from cluster_tools_tpu.tasks.base import count_block_rounds

    count_block_rounds({"flood": [np.array([3, 4, 99]), np.array([1, 2, 99])],
                        "cc": [np.array([2, 2, 99])]}, 2)
    got = metrics.snapshot()["counters"]
    assert got["blocks.computed"] == 2
    assert got["flood.rounds"] == 3 + 4 + 1 + 2
    assert got["flood.tile_rounds"] == 0
    assert got["cc.rounds"] == 4


def test_compiles_are_counted(traced):
    metrics.install_compile_cache_listener()

    @jax.jit
    def f(x):
        return jnp.sin(x) * 3.0

    f(np.arange(7, dtype=np.float32)).block_until_ready()
    got = metrics.snapshot()["counters"]
    assert got["jit.compiles"] >= 1
    assert got["jit.compile_s"] > 0


# -- the package never lowers, compiles or calls back -----------------------

_FORBIDDEN_ATTRS = {"runtime_executable", "as_text", "fingerprint",
                    "pure_callback", "io_callback"}


def _findings(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _FORBIDDEN_ATTRS:
            yield node.lineno, node.attr
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        attr, owner = node.func.attr, node.func.value
        # jitted.lower(*args); str.lower() takes none
        if attr == "lower" and (node.args or node.keywords):
            yield node.lineno, "lower"
        # lowered.compile(); re.compile(pattern) is another thing
        if attr == "compile" and not (isinstance(owner, ast.Name)
                                      and owner.id == "re"):
            yield node.lineno, "compile"
        if attr in ("callback", "print") and isinstance(
                owner, ast.Attribute) and owner.attr == "debug":
            yield node.lineno, f"debug.{attr}"


def test_package_never_lowers_compiles_or_calls_back():
    found = []
    for root, _, files in os.walk(PKG):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                found += [(os.path.relpath(path, PKG), *hit)
                          for hit in _findings(tree)]
    assert not found, found


# -- host spans on the profiler's clock -------------------------------------


class _CountingAnnotation:
    made = 0

    def __init__(self, name, **kwargs):
        type(self).made += 1
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_disabled_span_builds_no_annotation(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = 0
    assert not trace.enabled()
    span = trace.span("batch_compute", kind="host_compute")
    assert span is trace._NOOP
    with span:
        pass
    assert _CountingAnnotation.made == 0


def test_enabled_span_enters_an_annotation(monkeypatch, traced):
    from cluster_tools_tpu.obs.export import load_run

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = 0
    with trace.span("batch_compute", kind="host_compute"):
        pass
    assert _CountingAnnotation.made == 1
    trace.flush()
    spans = load_run(traced)["spans"]
    assert [s["name"] for s in spans] == ["batch_compute"]
    assert spans[0]["kind"] == "host_compute"


def test_monolithic_batch_spans_and_block_names(tmp_env, traced):
    """A one-batch dispatch runs a split-protocol task's stages in child
    spans of ``block_batch``, all timed on the host; a host block span
    carries its task in its name."""
    from cluster_tools_tpu.obs.export import load_run
    from cluster_tools_tpu.runtime import build
    from cluster_tools_tpu.runtime import config as cfg
    from cluster_tools_tpu.runtime.executor import run_split_batch
    from cluster_tools_tpu.runtime.task import BlockTask

    tmp_folder, config_dir = tmp_env

    class Staged(BlockTask):
        task_name = "staged_spans"

        def get_shape(self):
            return (4, 32, 32)

        def read_batch(self, block_ids, blocking, config):
            return list(block_ids)

        def compute_batch(self, payload, blocking, config):
            return payload

        def write_batch(self, result, blocking, config):
            pass

        def process_block_batch(self, block_ids, blocking, config):
            run_split_batch(self, block_ids, blocking, config)

        def process_block(self, block_id, blocking, config):
            pass

    class Plain(BlockTask):
        task_name = "plain_blocks"

        def get_shape(self):
            return (4, 32, 32)

        def process_block(self, block_id, blocking, config):
            pass

    cfg.write_global_config(config_dir, {
        "block_shape": [4, 32, 32], "target": "tpu", "devices": [0],
        "device_batch_size": 1})
    assert build([Staged(tmp_folder, config_dir)])
    cfg.write_global_config(config_dir, {
        "block_shape": [4, 32, 32], "target": "local"})
    assert build([Plain(tmp_folder + "_plain", config_dir)])
    trace.flush()
    spans = load_run(traced)["spans"]
    by_id = {s["id"]: s for s in spans}
    kinds = {s["name"]: s["kind"] for s in spans}
    assert kinds["block_batch"] == "host"
    assert kinds["batch_read"] == kinds["batch_write"] == "host_io"
    assert kinds["batch_compute"] == "host_compute"
    for name in ("batch_read", "batch_compute", "batch_write"):
        s = next(s for s in spans if s["name"] == name)
        assert by_id[s["parent"]]["name"] == "block_batch"
    assert kinds["block:plain_blocks"] == "host"


# -- the collective CC program (parallel/sharded.py) -------------------------

SCC_PHASES = ("scc.local", "scc.exchange", "scc.resolve")
SCC_SHAPE = (16, 96, 96)  # 2x2 default tiles per 4-plane shard


def _lower_scc():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cluster_tools_tpu.parallel.sharded import _sharded_cc

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    mask = jax.ShapeDtypeStruct(SCC_SHAPE, jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    return _sharded_cc.lower(mask, 1, "data", mesh)


@pytest.mark.parametrize("cc_mode", ["flat", "coarse"])
def test_collective_program_ops_hold_one_phase_scope(cc_mode):
    """Every operation of the collective CC program lies in exactly one of
    ``scc.local``, ``scc.exchange``, ``scc.resolve`` (the shard map's own
    ``sdy.return`` and closing line are structure, not operations); the
    coarse kernel's ``cc.*`` scopes nest inside ``scc.local``."""
    with _backend.force_cc_mode(cc_mode):
        text = _lower_scc().as_text(debug_info=True)
    paths = op_paths(text)
    assert len(paths) > 100
    seen, bad = set(), []
    for s, path in paths:
        got = phases_in(path or "", "scc.")
        seen |= got
        structure = s.startswith(("sdy.return", "}"))
        if len(got) != 1 and not structure and not (
                "stablehlo.constant" in s and not got):
            bad.append((path, s[:80]))
        if phases_in(path or "", "cc."):
            assert got == {"scc.local"}, path
    assert not bad, bad[:10]
    assert seen == set(SCC_PHASES)
    nested = {p for _, p in paths if p and phases_in(p, "cc.")}
    assert bool(nested) == (cc_mode == "coarse")
    exchange = "\n".join(s for s, p in paths
                         if phases_in(p or "", "scc.") == {"scc.exchange"})
    assert "collective_permute" in exchange and "all_gather" in exchange


def test_collective_tracing_changes_nothing_compiled(traced):
    def fresh():
        jax.clear_caches()
        return _lower_scc().as_text()

    on = fresh()
    trace.disable()
    assert fresh() == on


def test_collective_task_counts_and_spans(tmp_path, traced):
    """A run of the collective task adds its shards, stage-1 rounds and
    live face pairs to the counters and writes its four host spans."""
    from cluster_tools_tpu.obs.export import load_run
    from cluster_tools_tpu.runtime import build
    from cluster_tools_tpu.runtime import config as cfg
    from cluster_tools_tpu.tasks.thresholded_components import (
        ShardedComponentsTask,
    )
    from cluster_tools_tpu.utils import file_reader

    x = np.zeros((16, 16, 16), np.float32)
    x[:, 4, 4] = 1.0  # one column through every shard: 3 face pairs
    x[2, 10:14, 10] = 1.0
    path = str(tmp_path / "v.n5")
    file_reader(path).create_dataset("raw", data=x, chunks=(8, 16, 16))
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(config_dir, {
        "block_shape": [8, 16, 16], "devices": [0, 1, 2, 3]})
    task = ShardedComponentsTask(
        str(tmp_path / "tmp"), config_dir, input_path=path, input_key="raw",
        output_path=path, output_key="cc")
    assert build([task])
    got = metrics.snapshot()["counters"]
    assert got["scc.shards"] == 4
    assert got["scc.local_rounds"] >= 4
    assert got["scc.face_pairs"] == 3
    trace.flush()
    names = {s["name"] for s in load_run(traced)["spans"]}
    assert {"scc:ingest", "scc:program", "scc:relabel", "scc:write"} <= names
