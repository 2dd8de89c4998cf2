"""The trace by the engine's own names: a hand-written device trace with
known sums, laid out as a TPU profile lays it out (an op's ``tf_op`` is a
stat of its event metadata), each new per-layer reader on a hand-built
run, and a tiny traced run on the CPU that reads them through the
harness."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "bench")))

import _tiny  # noqa: E402
import engtrace as ET  # noqa: E402

STEP_KEYS = {"step": 1, "tf_op": 2}

#: Host spans (ns): three engine steps in one window; the first holds a
#: prefill launch, the other two only decode.
HOST = [
    ("bench.window", 0, 1000),
    ("engine.step", 0, 300), ("engine.prefill", 0, 20),
    ("engine.decode", 20, 40), ("engine.readback", 40, 200),
    ("engine.bookkeep", 200, 300),
    ("engine.step", 400, 700), ("engine.decode", 400, 420),
    ("engine.readback", 420, 600), ("engine.bookkeep", 600, 700),
    ("engine.step", 720, 950), ("engine.decode", 720, 740),
    ("engine.readback", 740, 900), ("engine.bookkeep", 900, 950),
]
#: Program launches on the module line (ns).
MODULES = [("jit_prefill_bucket_main_512x8(3)", 10, 60),
           ("jit_decode_main_12(7)", 60, 160),
           ("jit_decode_main_12(7)", 430, 530),
           ("jit_decode_main_12(7)", 750, 850)]
D = "jit(decode_main_12)/while/body/closed_call"
#: Device ops (ns): a while holding two scoped ops, an unscoped copy.
OPS = [("%fusion.10 = f32[] fusion()", 10, 60,
        "jit(prefill_bucket_main_512x8)/attn/attn_core/dot_general"),
       ("%while.1 = f32[] while()", 60, 140, "jit(decode_main_12)/while"),
       ("%fusion.1 = f32[] fusion()", 60, 90, f"{D}/attn/attn_core/dot"),
       ("%fusion.2 = f32[] fusion()", 90, 130, f"{D}/mlp/dot_general"),
       ("%copy.1 = f32[] copy()", 140, 160, None),
       ("%fusion.1 = f32[] fusion()", 430, 470, f"{D}/attn/attn_core/dot"),
       ("%fusion.3 = f32[] fusion()", 470, 530,
        "jit(decode_main_12)/sample/argmax"),
       ("%fusion.1 = f32[] fusion()", 750, 800, f"{D}/attn/attn_core/dot"),
       ("%fusion.4 = f32[] fusion()", 800, 850,
        "jit(decode_main_12)/head/dot_general")]


def _event(meta, start, end, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} {stats}}}")


def _plane(pid, name, lines):
    """One plane; an event given as (name, start, end, stats, tf_op)
    carries ``tf_op`` on its metadata, as a TPU profile does."""
    metas, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, s, e, stats, op in events:
            meta = metas.setdefault(ename, (len(metas) + 1, op))[0]
            evs.append(_event(meta, s, e, stats))
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    for n, (i, op) in metas.items():
        st = ("" if op is None else
              f"stats {{ metadata_id: 2 str_value: {json.dumps(op + ':')} }}")
        body.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: {json.dumps(n)} {st} }} }}")
    body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in STEP_KEYS.items()]
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) + " }"


def synthetic():
    host = [(n, s, e, "stats { metadata_id: 1 int64_value: 0 }"
             if n == "engine.step" else "", None) for n, s, e in HOST]
    mods = [(n, s, e, "", None) for n, s, e in MODULES]
    return "\n".join([
        _plane(1, "/host:CPU", [("python", host)]),
        _plane(2, "/device:TPU:0", [
            ("XLA Modules", mods),
            ("XLA Ops", [(n, s, e, "", op) for n, s, e, op in OPS])])])


def test_reduce_a_synthetic_trace(tmp_path, capsys):
    from jax.profiler import ProfileData
    text = synthetic()
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    r = ET.reduce(ET.load(str(tmp_path)))
    ns = 1e-9
    assert r["launches"] == {"prefill": 1, "decode": 3}
    assert r["programs"] == ["decode_main_12", "prefill_bucket_main_512x8"]
    # Leaf ops only: the while's 80 ns hold its body and do not count.
    want = {"decode/attn_core": 120, "decode/mlp": 40, "decode/sample": 60,
            "decode/head": 50, "decode/unattributed": 20,
            "prefill/attn_core": 50}
    assert r["scope_s"] == pytest.approx({k: v * ns for k, v in want.items()})
    assert r["device_s"] == pytest.approx({"decode": 290 * ns,
                                           "prefill": 50 * ns})
    assert r["unattributed_share"]["decode"] == pytest.approx(20 / 290)
    assert r["unattributed_share"]["prefill"] == 0
    assert r["op_name_source"] == {"tf_op": 7, "none": 1}   # leaves
    # The idle stretch 160-430 runs under step 0's readback and
    # bookkeeping, the harness between steps, then step 1's decode and
    # readback: each part goes to its own innermost span.
    assert r["idle_by_phase_s"] == pytest.approx(
        {"engine.prefill": 10 * ns, "engine.readback": 180 * ns,
         "engine.bookkeep": 250 * ns, "engine.decode": 40 * ns,
         "bench.window": 170 * ns})
    assert sum(r["idle_by_phase_s"].values()) == pytest.approx(r["idle_s"])
    assert r["idle_s"] == pytest.approx(650 * ns)
    # One pair of decode-only steps (1, 2): from 530 to 750.
    assert r["host_gaps_s"] == pytest.approx([220 * ns])
    # jax's ProfileData leaves metadata stats out: the same trace read
    # through it has programs, idle phases and gaps, but no scopes.
    p = ET.reduce(ProfileData.from_text_proto(text))
    for k in ("launches", "device_s", "idle_by_phase_s", "host_gaps_s"):
        assert p[k] == pytest.approx(r[k]), k
    assert p["op_name_source"] == {"none": 8}
    # The command line prints the same reduction of a kept trace.
    assert ET.main([str(tmp_path)]) == 0
    assert json.loads(capsys.readouterr().out)["scope_s"] == r["scope_s"]


def test_innermost_span_of_nested_spans():
    spans = sorted([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d")],
                   key=lambda s: (s[0], -s[1]))
    assert ET.innermost(spans, 0, 12) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "d"), (8, 10, "a"), (10, 12, "none")]
    assert ET.split_idle([(1, 7)], ET.innermost(spans, 0, 12)) == {
        "a": 2, "b": 2, "c": 1, "d": 1}


def test_names_of_programs_and_scopes():
    assert ET.program_of_module("jit_decode_main_12(881)") == "decode_main_12"
    assert ET.family("prefill_suffix_main_3x20") == "prefill"
    assert ET.family("f") == "other"
    assert ET.scope_of(f"{D}/attn/kv_write/scatter") == "kv_write"
    assert ET.scope_of("jit(f)/while/body/add") == ET.UNATTRIBUTED
    assert ET.op_path("%x = f32[] add(), metadata={op_name=\"a/mlp/add\"}",
                      {}) == ("a/mlp/add", "name")
    assert ET.op_path("%x", {"long_name": 'metadata={op_name="a/head/d"}'}) \
        == ("a/head/d", "long_name")


def _hand_run(**engtrace):
    """A run whose engine trace is given (``of`` reads it as is)."""
    base = {"launches": {"decode": 4, "prefill": 2},
            "device_s": {"decode": 0.2, "prefill": 3.0},
            "host_gaps_s": [0.003, 0.005]}
    base.update(engtrace)
    steps = [SimpleNamespace(traced=True, prefilled=p) for p in (0, 1200,
                                                                  800)]
    steps.append(SimpleNamespace(traced=False, prefilled=5000))
    return SimpleNamespace(trace={}, steps=steps, engtrace=base)


READS = [("decode_launch_ms.tail", 50.0),       # 0.2 s / 4 launches
         ("prefill_launch_ms_per_ktok", 1500.0),   # 3 s / 2000 tokens
         ("host_gap_ms.tail", 4.0)]             # mean of 3 and 5 ms


@pytest.mark.parametrize("name,want", READS)
def test_reader_on_a_hand_built_run(name, want):
    import spec
    m = spec.load_metric(name)
    assert m.read(_hand_run()) == pytest.approx(want)
    # Nothing to read: no trace, or programs that carry no names.
    assert m.read(SimpleNamespace(trace=None)) is None
    assert m.read(_hand_run(launches={"other": 9}, host_gaps_s=[])) is None


def test_admit_wait_reader_on_a_hand_built_run():
    import spec
    m = spec.load_metric("admit_wait_p90_ms")

    def span(sub, adm):
        ev = [SimpleNamespace(state="submitted", wall=sub),
              SimpleNamespace(state="queued", wall=sub)]
        if adm is not None:
            ev.append(SimpleNamespace(state="admitted", wall=adm))
        return SimpleNamespace(events=ev)

    spans = {i: span(10.0 + i, 10.0 + i + 0.1 * i) for i in range(9)}
    spans[9] = span(19.0, None)                  # never admitted
    eng = SimpleNamespace(telemetry=SimpleNamespace(span=spans.get))
    recs = [SimpleNamespace(rid=i, submit=1.0 + i) for i in range(10)]
    run = SimpleNamespace(window_recs=recs, end=12.0)
    # The reader finds ``eng`` beside ``run`` in this frame, as in the
    # harness's. Waits 0, 0.1, ..., 0.8 s and 2 s (run end 12.0 less
    # submission 10.0): the p90 is 0.8 s.
    assert m.read(run) == pytest.approx(800.0)
    del eng
    assert m.read(run) is None


NEW = ["decode_launch_ms.tail", "prefill_launch_ms_per_ktok",
       "host_gap_ms.tail", "admit_wait_p90_ms"]


def test_traced_tiny_run_reads_the_engine_names(tmp_path, monkeypatch):
    import run
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    write = _tiny.write

    def with_new_metrics(root):
        here = write(root)
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            bench = json.load(f)
        bench["per_layer"] += [{"name": n, "unit": "ms", "moves": "x",
                                "workloads": ["tiny.chat"]} for n in NEW]
        with open(path, "w") as f:
            json.dump(bench, f)
        return here

    monkeypatch.setattr(_tiny, "write", with_new_metrics)
    out = _tiny.execute(str(tmp_path), "tiny.chat", trace=True)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    # The CPU's ops name their module on each op (a TPU's, on the
    # module line): the launch metrics and the admission wait read.
    for n in ("decode_launch_ms.tail", "prefill_launch_ms_per_ktok",
              "admit_wait_p90_ms"):
        assert got[n]["value"] > 0, n
    # Submission comes at or after the due time and admission a moment
    # after the admitting step starts: the engine's wait is the harness's
    # give or take that moment.
    assert got["admit_wait_p90_ms"]["value"] <= \
        got["queue_wait_p90_ms"]["value"] + 50.0
