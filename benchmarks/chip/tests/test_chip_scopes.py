"""scopes.py and its readers, on a TPU-shaped trace written as a text
proto and on a trace of the training program recorded on the CPU."""
import pytest

import harness
import scopes
import trace

US = 1_000_000          # picoseconds in a microsecond

PREFIX = "jit(inner_chunk)/while/body/closed_call/"
CHUNK_OPS = {           # instruction -> op_name, as the program's HLO has it
    "while.1": "jit(inner_chunk)/while",
    "fusion.1": PREFIX + "vmap(jvp(model))/while/body/closed_call/"
                         "attention/dot_general",
    "fusion.2": PREFIX + "vmap(jvp(model))/lm_head/while/body/reduce_max",
    "while.2": PREFIX + "vmap(transpose(jvp(model)))/while",
    "fusion.3": PREFIX + "vmap(transpose(jvp(model)))/while/body/closed_call/"
                         "checkpoint/rematted_computation/mlp/dot_general",
    "fusion.4": PREFIX + "vmap(transpose(jvp(model)))/while/body/closed_call/"
                         "checkpoint/attention/transpose",
    "while.3": PREFIX + "vmap(inner_opt)/muon/newton_schulz/while",
    "fusion.5": PREFIX + "vmap(inner_opt)/muon/newton_schulz/while/body/"
                         "closed_call/dot_general",
    "fusion.6": PREFIX + "vmap(inner_opt)/adamw/add",
}
OUTER_OPS = {"fusion.1": "jit(outer_step_ef)/outer_step/sub"}

# (program, instruction, start us, end us) on the XLA Ops line: the chunk's
# loop nests everything; the backward loop nests two ops, Newton-Schulz's
# an op and a copy that names no scope, and starts before the backward
# loop's recorded end; another such copy sits in the chunk's loop itself
DEVICE_OPS = [
    ("chunk", "while.1", 0, 100), ("chunk", "fusion.1", 5, 25),
    ("chunk", "fusion.2", 25, 35), ("chunk", "while.2", 35, 75),
    ("chunk", "fusion.3", 40, 60), ("chunk", "fusion.4", 60, 70),
    ("chunk", "while.3", 74.5, 95), ("chunk", "fusion.5", 76, 90),
    ("chunk", "copy.1", 90, 94), ("chunk", "fusion.6", 95, 98),
    ("chunk", "copy.2", 98, 100),
    ("outer", "fusion.1", 103, 113),
    ("chunk2", "fusion.1", 120, 124), ("chunk2", "fusion.6", 126, 130),
]
PROGRAMS = {"chunk": ("jit_inner_chunk(7)", 0, 100),
            "outer": ("jit_outer_step_ef(9)", 103, 113),
            "chunk2": ("jit_inner_chunk(7)", 120, 130)}
HOST_SPANS = [("train", 88, 125), ("trainer.fetch", 90, 101.5),
              ("trainer.sync", 101.5, 104), ("trainer.data", 104.5, 118),
              ("trainer.chunk", 118, 121)]
WINDOW_S = 140e-6       # 10 us beyond the first-to-last device span

EXPECTED_SCOPES_US = {
    "none": 5 + 2,                       # while.1 less what it nests;
                                         # copy.2 inside it
    "model/attention": 20 + 4, "model/lm_head": 10,
    "transpose(model)": 10 - 0.5,        # while.2 less what starts in it
    "transpose(model)/mlp": 20, "transpose(model)/attention": 10,
    "inner_opt/muon/newton_schulz": 2.5 + 14 + 4,   # copy.1 inherits
    "inner_opt/adamw": 3 + 4,
    "outer_step": 10,
}
EXPECTED_IDLE_US = {
    "trainer.fetch": 1.5, "trainer.sync": 1.5,     # gap 100-103
    "trainer.data": 5, "trainer.chunk": 2,         # gap 113-120
    "train": 1, "none": 1 + 10,                    # gap 124-126, edges
}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto(name: str, ops) -> bytes:
    instrs = b"".join(_field(2, _field(1, i.encode())
                             + _field(7, _field(2, op.encode())))
                      for i, op in ops.items())
    module = _field(1, name.encode()) + _field(3, _field(1, b"main")
                                                + instrs)
    return _field(1, module)


def _octal(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


def _line(lid, name, events, ids):
    evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * US)}"
                  f" duration_ps: {int((e - s) * US)} }}\n"
                  for n, s, e in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n{evs}}}\n'


def _metadata(ids, stats=lambda name: ""):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" {stats(n)}}} }}\n' for n, i in ids.items())


def _text_proto(hlo: bool = True, spans: bool = True) -> str:
    ops = [(f"{PROGRAMS[p][0]}|{i}", s, e) for p, i, s, e in DEVICE_OPS]
    mods = list(PROGRAMS.values())
    dev_ids = {n: k + 1 for k, n in enumerate(dict.fromkeys(
        [n.split("|")[1] for n, _, _ in ops] + [m[0] for m in mods]))}
    device = ('planes { id: 1 name: "/device:TPU:0"\n'
              + _line(1, "XLA Modules", mods, dev_ids)
              + _line(2, "XLA Ops", [(n.split("|")[1], s, e)
                                     for n, s, e in ops], dev_ids)
              + _metadata(dev_ids) + "}\n")
    host_events = HOST_SPANS if spans else []
    host_ids = {n: k + 1 for k, n in enumerate(dict.fromkeys(
        [n for n, _, _ in host_events] + ["PjitFunction(inner_chunk)"]))}
    host = ('planes { id: 2 name: "/host:CPU"\n'
            + _line(1, "python", host_events
                    + [("PjitFunction(inner_chunk)", 80, 126)], host_ids)
            + _metadata(host_ids) + "}\n")
    protos = {"jit_inner_chunk(7)": _hlo_proto("jit_inner_chunk", CHUNK_OPS),
              "jit_outer_step_ef(9)": _hlo_proto("jit_outer_step_ef",
                                                 OUTER_OPS)}
    meta = ('planes { id: 3 name: "/host:metadata"\n'
            + _metadata({n: k + 1 for k, n in enumerate(protos)},
                        lambda n: f'stats {{ metadata_id: 1 bytes_value: '
                                  f'"{_octal(protos[n])}" }} ')
            + 'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }\n'
            + "}\n")
    return device + host + (meta if hlo else "")


def _write(tmp_path, text: str) -> str:
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def tpu_trace(tmp_path):
    return _write(tmp_path, _text_proto())


def test_self_time_excludes_nested_ops(tpu_trace):
    red = scopes.reduce_scopes(tpu_trace, window_s=WINDOW_S)
    got = {k: round(v * 1e6, 6) for k, v in red["scopes"].items()}
    assert got == EXPECTED_SCOPES_US
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"])


def test_idle_goes_to_innermost_span(tpu_trace):
    red = scopes.reduce_scopes(tpu_trace, window_s=WINDOW_S)
    got = {k: round(v * 1e6, 6) for k, v in red["idle_by_span"].items()}
    assert got == EXPECTED_IDLE_US
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        WINDOW_S - red["busy_s"])


def test_busy_agrees_with_reduce_trace(tpu_trace):
    red = scopes.reduce_scopes(tpu_trace, window_s=WINDOW_S)
    base = trace.reduce_trace(tpu_trace, window_s=WINDOW_S)
    assert red["busy_s"] == base["busy_s"]
    assert base["window_s"] == WINDOW_S


@pytest.mark.parametrize("name, want", [
    ("forward_ms.train", (24 + 10) / 2 / 1000),
    ("backward_ms.train", (9.5 + 20 + 10) / 2 / 1000),
    ("optimizer_ms.train", (20.5 + 7) / 2 / 1000),
    ("input_wait_ms.train", 5 / 1 / 1000),
])
def test_reader(tpu_trace, monkeypatch, name, want):
    monkeypatch.setattr(scopes, "newest_trace", lambda: tpu_trace)
    run = {"steps": 2, "rounds": 1, "trace": {"window_s": WINDOW_S}}
    assert harness.reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["forward_ms.train", "backward_ms.train",
                                  "optimizer_ms.train",
                                  "input_wait_ms.train"])
def test_reader_silent_without_names(tmp_path, monkeypatch, name):
    """A program that names no scope and records no span (the parent of
    this instrumentation) reads as no metric, not as an error."""
    path = _write(tmp_path, _text_proto(hlo=False, spans=False))
    monkeypatch.setattr(scopes, "newest_trace", lambda: path)
    run = {"steps": 2, "rounds": 1, "trace": {"window_s": WINDOW_S}}
    assert harness.reader(name)(run) is None


def test_scope_path_rules():
    assert scopes.scope_path("") == "none"
    assert scopes.scope_path("jit(f)/while/body/add") == "none"
    assert scopes.scope_path(CHUNK_OPS["fusion.4"]) == \
        "transpose(model)/attention"
    # a sub-jit's name is not a scope: jnp.clip inside AdamW
    assert scopes.scope_path(PREFIX + "vmap(inner_opt)/adamw/jit(clip)/max") \
        == "inner_opt/adamw"
    # XLA merged two ops: the first one's path
    assert scopes.scope_path("vmap(jvp(model))/lm_head/transpose;"
                             "vmap(inner_opt)/clip") == "model/lm_head"


@pytest.fixture(scope="module")
def program_trace(tmp_path_factory):
    """Two DiLoCo rounds of the program at a tiny size, traced on the
    CPU, as run.py traces the window."""
    import jax
    from repro.configs.base import (DiLoCoConfig, ModelConfig,
                                    OptimizerConfig)
    from repro.core import DiLoCoSync, DistTrainer
    from repro.models.transformer import build_model, init_params

    cfg = ModelConfig(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=61, loss_chunk=8)
    dt = DistTrainer(build_model(cfg).loss,
                     OptimizerConfig(total_steps=20, warmup_steps=0),
                     DiLoCoConfig(num_workers=1, h_inner_steps=2),
                     DiLoCoSync())

    def data(step):
        t = jax.random.randint(jax.random.key(step), (1, 1, 16), 0, 61)
        return {"tokens": t, "labels": t}

    state, _ = dt.run(dt.init(init_params(cfg, jax.random.key(0))[0]),
                      data, 2, consume=True)
    d = str(tmp_path_factory.mktemp("program"))
    jax.profiler.start_trace(d)
    dt.run(state, data, 4, consume=True)
    jax.profiler.stop_trace()
    return trace.find_xplane(d)


def test_program_trace_names_its_layers(program_trace):
    base = trace.reduce_trace(program_trace, layout=trace.CPU)
    red = scopes.reduce_scopes(program_trace, layout=trace.CPU,
                               window_s=base["window_s"])
    firsts = {k.split("/")[0] for k in red["scopes"]}
    assert {"model", "transpose(model)", "inner_opt",
            "outer_step"} <= firsts, firsts
    assert "inner_opt/muon/newton_schulz" in red["scopes"]
    assert {"trainer.data", "trainer.chunk"} <= set(red["idle_by_span"])
    assert red["busy_s"] == base["busy_s"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_reduce_trace_keys_unchanged(program_trace):
    """The accepted reduction reads as before: its keys are the ones the
    benchmark's result line and readers use."""
    red = trace.reduce_trace(program_trace, layout=trace.CPU)
    assert set(red) == {"devices", "busy_s", "window_s", "programs", "ops",
                        "top_ops", "idle_gaps"}
