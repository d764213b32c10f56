"""Scope reduction (bench/scopes.py) and the readers of the program's own
records, on hand-made inputs with a known answer; the scope names read from
the program's source; the fusion rule on a decode program compiled for a
described TPU v5e."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from bench import cells, scopes
from bench import trace_reduce as tr
from bench.scopes import Op
from bench.trace_reduce import Interval as I

STEP = "jit(_engine_step_impl)/while/body/"


def _scoped_trace():
    """Two decode-block calls in the window, one before it, and a prefill
    call; ops carry their name paths."""
    t = tr.Trace()
    t.devices["/device:TPU:0"] = {
        "ops": [Op("fusion.9", 0.5, 1.5, "", STEP + "layers/while/body/"
                   "attention/dot_general"),                # call before lo
                Op("while.1", 2.0, 4.0, ""),                # a container
                Op("dynamic-slice.2", 2.0, 2.5, "",
                   STEP + "layers/while/body/dynamic_slice"),
                Op("fusion.3", 2.5, 3.25, "", STEP + "layers/while/body/"
                   "attention/dot_general"),
                Op("fusion.4", 3.25, 3.5, "",
                   STEP + "layers/while/body/jvp(mlp)/mul"),
                Op("fusion.5", 3.5, 3.75, "", STEP + "sample/argmax"),
                Op("copy.6", 3.75, 4.0, ""),                # no path
                Op("fusion.7", 5.0, 6.0, "", "jit(_prefill_impl)/head/dot"),
                Op("fusion.8", 9.0, 11.0, "",
                   STEP + "layers/while/body/dynamic_slice")],
        "modules": [I("jit__engine_step_impl(9)", 0.5, 1.5),
                    I("jit__engine_step_impl(9)", 2.0, 4.0),
                    I("jit__prefill_impl(7)", 5.0, 6.0),
                    I("jit__engine_step_impl(9)", 9.0, 11.0)],
    }
    t.host = [I("bench.traced", 1.0, 10.0)]
    return t


def test_reduce_charges_each_op_to_its_innermost_scope():
    t = _scoped_trace()
    got = scopes.reduce(t)
    # the calls that start in the window; the last one clipped at its close
    assert got == {
        "jit__engine_step_impl": {"layers": pytest.approx(0.5 + 1.0),
                                  "attention": pytest.approx(0.75),
                                  "mlp": pytest.approx(0.25),
                                  "sample": pytest.approx(0.25),
                                  "other": pytest.approx(0.25)},
        "jit__prefill_impl": {"head": pytest.approx(1.0)}}
    calls, secs = tr.module_time(tr.reduce(t), "_engine_step_impl")
    assert calls == 2
    assert sum(got["jit__engine_step_impl"].values()) == pytest.approx(secs)


def test_reduce_of_a_trace_without_ops_is_empty():
    assert scopes.reduce(tr.Trace()) == {}


@pytest.mark.parametrize("path,want", [
    (STEP + "layers/while/body/attention/dot_general", "attention"),
    (STEP + "layers/while/body/dynamic_slice", "layers"),
    ("jit(f)/transpose(jvp(mlp))/mul", "mlp"),
    ("jit(f)/pages/scatter-add", "pages"),
    ("jit(f)/embed/gather", "embed"),
    ("jit(f)/while/body/add", "other"),
    ("", "other")])
def test_scope_of_a_path(path, want):
    assert scopes.scope(path) == want


def test_the_programs_scope_names_are_read_from_its_source():
    assert {"embed", "layers", "attention", "mlp", "head", "sample",
            "pages"} <= scopes.program_scopes()


def test_every_scope_the_program_names_is_a_literal():
    assert scopes.unread_scopes() == []


def test_a_scope_the_program_adds_is_charged_without_a_change_here(
        tmp_path):
    (tmp_path / "moe.py").write_text(
        "import jax\nfrom jax import named_scope\n\n\n"
        "def ffn(x, name):\n"
        "    with jax.named_scope('mlp'):\n"
        "        with named_scope(\"experts\"):\n"
        "            x = x + 1\n"
        "        with jax.named_scope(name):\n"   # not a literal: not read
        "            return x\n")
    names = scopes.program_scopes(str(tmp_path))
    assert names == {"mlp", "experts"}
    assert scopes.unread_scopes(str(tmp_path)) == [f"{tmp_path}/moe.py:9"]
    assert scopes.scope("jit(f)/mlp/experts/dot_general", names) == "experts"
    assert scopes.scope("jit(f)/mlp/dot_general", names) == "mlp"


# a few protobuf fields, written by hand
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _field(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _ins(iid, name, opcode, path="", ops=(), calls=()):
    out = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if path:
        out += _field(7, _field(2, path))
    if ops:
        out += _field(36, b"".join(_varint(o) for o in ops))
    for c in calls:
        out += _field(38, c)                       # unpacked also reads
    return out


def _comp(cid, root, *ins):
    return _field(5, cid) + _field(6, root) + b"".join(
        _field(2, i) for i in ins)


def _module():
    att = "jit(f)/layers/attention/jit(_take)/select_n"
    fused_tuple = _comp(
        2, 13, _ins(10, "param_0", "parameter"),
        _ins(11, "select.1", "select", att, ops=(10,)),
        _ins(12, "convert.2", "convert", ops=(11,)),       # XLA's own
        _ins(13, "tuple.3", "tuple", ops=(12, 11)))
    fused_root = _comp(3, 21, _ins(20, "param_0", "parameter"),
                       _ins(21, "add.4", "add", "jit(f)/mlp/add", ops=(20,)))
    entry = _comp(
        1, 3, _ins(1, "p", "parameter", "cache['kp']"),
        _ins(2, "select_convert_fusion.2", "fusion", ops=(1,), calls=(2,)),
        _ins(3, "fusion.5", "fusion", ops=(2,), calls=(3,)),
        _ins(4, "copy.6", "copy", ops=(1,)))
    return b"".join(_field(3, c) for c in (entry, fused_tuple, fused_root))


def test_a_fusion_without_op_name_takes_the_first_one_back_from_its_root():
    got = scopes.module_paths(_module())
    # the tuple root and the inserted convert carry none: the select does
    assert scopes.scope(got["select_convert_fusion.2"]) == "attention"
    assert got["fusion.5"] == "jit(f)/mlp/add"         # its root's own
    assert got["copy.6"] == "" and got["convert.2"] == ""


def test_hlo_paths_read_the_module_from_the_host_metadata_plane():
    meta = (_field(1, 77) + _field(2, "jit_f(77)")
            + _field(5, _field(1, 1) + _field(6, _field(1, _module()))))
    plane = _field(1, 5) + _field(2, "/host:metadata") + _field(
        4, _field(1, 77) + _field(2, meta))
    raw = _field(1, plane) + _field(1, _field(2, "/host:CPU"))
    got = scopes.hlo_paths(raw)
    assert list(got) == [77]
    assert scopes.scope(got[77]["select_convert_fusion.2"]) == "attention"
    assert scopes.program_id("jit__engine_step_impl(77)") == 77
    assert scopes.program_id("jit_f") is None


def test_paths_come_from_a_traced_programs_hlo(tmp_path):
    def f(x, w):
        def body(c, wl):
            with jax.named_scope("attention"):
                c = jnp.tanh(c @ wl)
            return c, c
        with jax.named_scope("layers"):
            c, ys = jax.lax.scan(body, x, w)
        return c.sum() + ys.sum()

    g = jax.jit(f)
    args = jnp.ones((8, 32)), jnp.ones((3, 32, 32))
    g(*args).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        g(*args).block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        progs = scopes.hlo_paths(fh.read())
    got = {scopes.scope(p) for names in progs.values()
           for p in names.values() if p.startswith("jit(f)/")}
    assert {"layers", "attention"} <= got


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with the persistent compilation cache
    off: entries compiled for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_paged_page_gather_and_its_f32_convert_read_as_attention(one_chip):
    """The batch cell's decode (cut to tiny widths) compiled for the TPU:
    XLA fuses the gather of each row's page table with its f32 convert
    into a multi-output fusion that has no op_name of its own. Every op
    that writes the gathered table must still read as `attention`."""
    from bench.drivers import serve_engine
    from bench.tests import tiny
    from repro.serving import ServingEngine

    c = tiny.cell("minicpm2b.batch")
    cfg, fns = serve_engine.program_config(c.config)
    eng = ServingEngine(cfg, fns, fns.init(jax.random.PRNGKey(0), cfg),
                        serve_engine.engine_config(c.traffic["engine"]))
    on_chip = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    comp = jax.jit(eng._engine_step_impl).lower(
        on_chip(eng.params), on_chip(eng.cache), on_chip(eng.state)).compile()
    paths = scopes.module_paths(
        comp.runtime_executable().hlo_modules()[0]
        .as_serialized_hlo_module_proto())
    (b, mp), kp = eng.cache["ptab"].shape, eng.cache["kp"].shape
    table = f"[{b},{mp},{kp[2]},{kp[3]},{kp[4]}]"
    text = comp.as_text()
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found = {}
    for block in re.split(r"\n(?=\S)", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+)", block)
        if not head or head.group(1) in fused:
            continue
        for m in re.finditer(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*?) fusion\(",
                             block, re.M):
            if table in m.group(2):
                found[m.group(1)] = scopes.scope(paths.get(m.group(1), ""))
    assert any(f"f32{table}" in ln and "fusion(" in ln
               for ln in text.splitlines()), "no f32 convert of the table"
    assert found and set(found.values()) == {"attention"}, found


# --------------------------------------------------------------------------
# the readers of the program's own records
# --------------------------------------------------------------------------
RED = {"window_s": 4.0, "busy_s": 3.0,
       "modules": {"jit__prefill_impl": [2, 0.5],
                   "jit__engine_step_impl": [10, 2.0]},
       "scopes": {"jit__prefill_impl": {"layers": 0.25, "other": 0.25},
                  "jit__engine_step_impl": {"layers": 0.8, "attention": 0.6,
                                            "mlp": 0.4, "other": 0.2}}}
CTX = {"trace": RED, "decode_block": 8,
       "queue_waits": [0.1 * k for k in range(11)],
       "counters": {"prefill_tokens": 48, "prefill_slot_tokens": 64 * 48}}
SCOPED = ["decode_scan_self_ms.chat", "decode_scan_self_ms.batch",
          "decode_attention_ms.chat", "decode_attention_ms.batch"]


def test_decode_ms_lists_the_decode_programs_scopes_longest_first():
    assert scopes.decode_ms(CTX) == [
        ["layers", pytest.approx(10.0)], ["attention", pytest.approx(7.5)],
        ["mlp", pytest.approx(5.0)], ["other", pytest.approx(2.5)]]
    assert scopes.decode_ms(dict(CTX, trace=dict(RED, scopes={}))) is None
    assert scopes.decode_ms({}) is None


@pytest.mark.parametrize("name", SCOPED)
def test_scope_reader_without_scope_names_reads_nothing(name):
    # a program without named scopes: every op lands in `other`; or a
    # reduction that keeps no scopes at all
    red = dict(RED, scopes={"jit__engine_step_impl": {"other": 2.0}})
    assert cells.metric_reader(name)(dict(CTX, trace=red)) is None
    red = {k: v for k, v in RED.items() if k != "scopes"}
    assert cells.metric_reader(name)(dict(CTX, trace=red)) is None


@pytest.mark.parametrize("name", ["queue_wait_p90_ms.chat",
                                  "prefill_useful.chat"])
def test_counter_reader_without_stamps_or_counters_reads_nothing(name):
    ctx = dict(CTX, queue_waits=[], counters=None)
    assert cells.metric_reader(name)(ctx) is None
