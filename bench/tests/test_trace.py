"""bench/trace.py reduces traces to known busy, idle and kernel times."""
import pytest
from jax.profiler import ProfileData

from bench import trace

MS = 10 ** 9                      # picoseconds per millisecond


def _line(lid, name, events):
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                  f"duration_ps: {int((e - s) * MS)} }}\n"
                  for m, s, e in events)
    return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0\n{evs}}}\n"


def _plane(pid, name, lines, names):
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: \"{n}\" }} }}\n" for i, n in names.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n{lines}{meta}}}\n"


def synthetic():
    """One horizon [0, 10) ms and one prefill chunk [15, 25) ms; the
    host opens the spans the drivers open."""
    dev_names = {1: "jit_decode_horizon_step(11)",
                 2: "jit_prefill_chunk_step(12)", 3: "fusion.1",
                 4: "paged_attention.7", 5: "fusion.2"}
    dev = _plane(1, "/device:TPU:0",
                 _line(1, "XLA Modules", [(1, 0, 10), (2, 15, 25)]) +
                 _line(2, "XLA Ops", [(3, 0, 2), (4, 2, 5), (5, 6, 10),
                                      (4, 15, 20), (3, 20, 25)]),
                 dev_names)
    host_names = {1: "scheduler.step", 2: "server.horizon_batch",
                  3: "server.prefill_chunk", 4: "bench.wait_arrival",
                  5: "PjitFunction(decode_horizon_step)"}
    host = _plane(2, "/host:CPU",
                  _line(1, "python", [(1, 0, 26), (2, 0, 11), (5, 0, 1),
                                      (3, 14, 26), (4, 26, 40)]),
                  host_names)
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(dev + host))


def test_synthetic_trace_reduces_to_known_times():
    tr = trace.reduce_data(synthetic(), 100.0, 100.040)
    assert tr["window_s"] == pytest.approx(0.040)
    assert tr["busy_s"] == pytest.approx(0.019)        # 2+3+4 + 5+5 ms
    assert tr["modules"] == pytest.approx(
        {"decode_horizon_step": 0.010, "prefill_chunk_step": 0.010})
    assert tr["kernels"]["paged_attention"] == pytest.approx(
        {"decode_horizon_step": 0.003, "prefill_chunk_step": 0.005})
    assert tr["kernel_calls"] == {"paged_attention": 2}
    ops = dict(tr["breakdown"]["device_ops"])
    assert ops == pytest.approx({
        "decode_horizon_step/fusion": 0.006,
        "prefill_chunk_step/paged_attention": 0.005,
        "prefill_chunk_step/fusion": 0.005,
        "decode_horizon_step/paged_attention": 0.003})
    # the 1 ms gap inside the horizon and the 5 ms between the programs
    assert dict(tr["breakdown"]["idle_gaps"]) == pytest.approx(
        {"server.horizon_batch": 0.001, "scheduler.step": 0.005})


def test_kernel_named_by_stat_only():
    assert trace.kernel_of("custom-call.3",
                           [("long_name", "%x = tpu_custom_call(...) "
                             "kernel_name=topk_scan")]) == "topk_scan"
    assert trace.kernel_of("paged_attention_q8.1", []) == \
        "paged_attention_q8"
    assert trace.kernel_of("fusion.9", [("long_name", "add")]) is None


def test_module_names():
    assert trace.module_name("jit_decode_horizon_step(123)") == \
        "decode_horizon_step"
    assert trace.module_name("jit__topk_jit(4)") == "_topk_jit"


def test_recorded_chip_trace():
    """``data/probe.xplane.pb``: a TPU v5e trace recorded by
    ``record_trace.py`` (granite-3-2b widths, 2 layers): two scheduler
    iterations — one H=8 horizon, so 2 layers x 8 steps = 16 paged
    attention calls — a short sleep, and one top-k job."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "probe.xplane.pb")
    tr = trace.reduce(path)
    assert tr["n_devices"] == 1
    assert tr["kernel_calls"] == {"paged_attention": 16, "topk_scan": 1}
    assert tr["busy_s"] == pytest.approx(0.006910592, rel=1e-6)
    assert tr["window_s"] == pytest.approx(0.017358683, rel=1e-6)
    assert tr["modules"]["decode_horizon_step"] == pytest.approx(
        0.006724077, rel=1e-6)
    assert tr["kernels"]["paged_attention"] == pytest.approx(
        {"decode_horizon_step": 0.000161279}, rel=1e-5)
    assert tr["kernels"]["topk_scan"] == pytest.approx(
        {"_topk_jit": 0.000187056}, rel=1e-5)
    gaps = dict(tr["breakdown"]["idle_gaps"])
    # the device idles through the host's sleep and around each call
    assert gaps["bench.wait"] == pytest.approx(0.005266288, rel=1e-5)
    assert set(gaps) >= {"bench.step", "bench.submit_jobs"}
    assert tr["busy_s"] + sum(gaps.values()) <= tr["window_s"]
