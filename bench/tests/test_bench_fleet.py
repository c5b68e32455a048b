"""The harness on a fleet of one-chip replicas, and a configuration's
whole model block.

The fleet checks need four devices; a process that has touched JAX keeps
the one it has, so they run fleet_probe.py once, in a process of its own
with four host-platform CPU devices, and judge what it wrote. The rest
(one-chip builds, `model_fields`, `config_numbers`, the fleet readers on
synthetic traces, the token sample) run here."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

import bench_fixture
import pytest

from bench.lib import check, serve, spec
from bench.lib import trace as T

SPAN_METRICS = ("admission_wait_p90_ms", "dpu_queue_p90_ms",
                "dpu_exec_p90_ms", "admit_handoff_p90_ms")


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    out = tmp_path_factory.mktemp("fleet") / "fleet.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.pathsep.join([str(bench_fixture.REPO / "src"),
                                           str(bench_fixture.REPO)]))
    p = subprocess.run([sys.executable, str(bench_fixture.HERE /
                                            "fleet_probe.py"), str(out)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(out.read_text())


def test_four_chip_cell_builds_four_slices_each_on_its_own_device(fleet):
    assert fleet["devices"] == [0, 1, 2, 3]
    assert fleet["fleet_type"] == "MultiSliceEngine"
    assert fleet["slice_types"] == ["ServingEngine"] * 4
    assert fleet["params_on"] == [[0], [1], [2], [3]]


def test_every_slice_serves_the_benchmarks_weights(fleet):
    assert fleet["weights_equal"] == [True] * 4
    assert fleet["altered_replica_refused"] is True


def test_warm_up_reaches_every_slice_with_every_prompt_bucket(fleet):
    by_slice = fleet["admit_buckets_by_slice"]
    assert sorted(by_slice) == ["0", "1", "2", "3"]
    assert all(b == [8, 16, 32] for b in by_slice.values())


def test_tokens_served_is_the_sum_over_the_slices(fleet):
    """Step by step, the harness's count moves as the sum of the slices'
    own `tokens_served` counters, and ends at the served budgets."""
    checks = fleet["tokens_checks"]
    base_h, base_c = fleet["tokens_before"]
    assert len(checks) > 2
    for h, c in checks:
        assert h - base_h == c - base_c
    assert checks[-1][0] - base_h == fleet["tokens_budget"]
    assert all(n > 0 for n in fleet["admitted_by_slice"])


def test_counters_sum_over_the_slices_with_the_fleets_beside(fleet):
    assert fleet["counters_admitted"] == sum(fleet["admitted_by_slice"])
    assert fleet["counters_fleet_dispatched"] > 0


def test_traced_fleet_run_is_correct(fleet):
    tr = fleet["traced"]
    assert tr["correct"] is True and tr["failed"] == 0
    assert tr["completed"] == tr["attempted"] > 0
    assert tr["completed_slices"] == ["0", "1", "2", "3"]
    # no TPU plane on the CPU: the device readers find nothing to read
    assert tr["metrics"] == sorted(SPAN_METRICS)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_program_span_metric_counts_every_slices_requests(fleet, name):
    tr = fleet["traced"]
    assert tr["samples"][name] == tr["completed"]


def test_token_sample_reaches_every_slice(fleet):
    assert fleet["traced"]["sample_slices"] == ["0", "1", "2", "3"]


def test_tokens_altered_on_one_slice_are_caught(fleet):
    f = fleet["faulty"]
    assert f["correct"] is False
    assert f["token_gap"]["value"] > f["token_gap"]["limit"]


# --- in this process -----------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.make_root(tmp_path_factory.mktemp("bench"))


def test_one_chip_cell_builds_one_engine_through_the_same_call(root):
    import jax

    from bench.lib import weights
    from repro.serving import runtime as runtime_mod
    from repro.serving.engine import ServingEngine

    cell = spec.load_cell("tiny-dense.batch", root)
    cfg = serve.model_config(cell.config)
    params = weights.init_params(cfg, 5, jax.devices()[0])
    calls = []
    real = runtime_mod.build_pipelined_runtime

    def spy(*a, **k):
        calls.append(k)
        return real(*a, **k)

    with mock.patch.object(runtime_mod, "build_pipelined_runtime", spy):
        sysm = serve.build(cell.config, params, with_audio=False,
                           chips=cell.chips)
    assert [sorted(k) for k in calls] == [["ec", "params", "rc", "service"]]
    assert type(sysm.engine) is ServingEngine
    assert sysm.engines == [sysm.engine] and sysm.engine.params is params
    sysm.runtime.close()


def test_run_refuses_fewer_devices_than_the_cell_asks_for(root):
    import importlib.util

    s = importlib.util.spec_from_file_location("bench_run",
                                               bench_fixture.BENCH / "run.py")
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    with pytest.raises(SystemExit, match="needs 4"):
        run.run_cell(spec.load_cell("tiny-ssm.voice_fleet4", root), 1, 1.0,
                     allow_cpu=True, t_start=time.monotonic())


@pytest.mark.parametrize("reduced,chips,want", [
    ([], 1, 1), ([], 4, 4), (["n_layers"], 1, 1), (["n_layers"], 4, None)])
def test_fleet_size(reduced, chips, want):
    config = {"name": "m", "reduced": reduced}
    if want is None:
        with pytest.raises(ValueError, match="sharded build"):
            serve.fleet_size(config, chips)
    else:
        assert serve.fleet_size(config, chips) == want


def _real_config(name):
    return json.loads((bench_fixture.BENCH / "configs" /
                       f"{name}.json").read_text())


def test_model_fields_are_applied_after_the_top_level_keys():
    config = dict(_real_config("granite-3-8b-pp2"), model_fields={
        "n_experts": 9, "top_k": 2, "moe_d_ff": 768, "attn_every": 10,
        "attn_offset": 5, "attn_dp_axes": ["data"]})
    cfg = serve.model_config(config)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (9, 2, 768)
    assert (cfg.attn_every, cfg.attn_offset) == (10, 5)
    assert cfg.attn_dp_axes == ("data",)
    assert cfg.n_layers == 20 and cfg.d_ff == 12800
    n = serve.config_numbers(cfg)
    assert n["n_experts"] == 9 and n["attn_offset"] == 5


@pytest.mark.parametrize("fields", [{"n_expert": 8}, {"no_such": 1},
                                    {"n_layers": 10}, {"d_model": 64}])
def test_model_fields_refuses_unknown_or_covered_fields(fields):
    config = dict(_real_config("mamba2-370m"), model_fields=fields)
    with pytest.raises(ValueError, match="model_fields"):
        serve.model_config(config)


@pytest.mark.parametrize("name", ["granite-3-8b-pp2", "mamba2-370m"])
def test_existing_files_build_the_same_model(name):
    from dataclasses import replace

    from repro.configs.base import get_config

    config = _real_config(name)
    assert "model_fields" not in config
    base = get_config(config["model"])
    before = replace(base, **{k: config[k] for k in serve.MODEL_KEYS
                              if k in config})
    assert serve.model_config(config) == before


@pytest.mark.parametrize("name", ["granite-3-8b-pp2", "mamba2-370m"])
def test_config_numbers_is_a_superset_of_the_model_keys(name):
    cfg = serve.model_config(_real_config(name))
    before = {k: getattr(cfg, k) for k in serve.MODEL_KEYS
              if hasattr(cfg, k)} | {"head_dim": cfg.hd}
    now = serve.config_numbers(cfg)
    assert {k: now[k] for k in before} == before
    assert set(now) > set(before) and now["name"] == cfg.name


def _chips(intervals_by_chip, window=(0.0, 4.0)):
    devs = []
    for i, iv in enumerate(intervals_by_chip):
        d = T.DeviceTrace(f"/device:TPU:{i}")
        d.ops = [(f"%fusion.{i} = f32[8] fusion(x)", s, e) for s, e in iv]
        d.modules = [(f"jit__segment({i})", s, e) for s, e in iv]
        devs.append(d)
    return T.Trace(devices=devs, host_spans=[("bench.window", *window),
                                             ("bench.segment", 0.0, 2.0)],
                   window=window)


OVERLAP = spec.load_module(bench_fixture.BENCH, "metrics",
                           "chip_overlap.fleet4")


@pytest.mark.parametrize("layout,want", [
    # one after another: each chip busy 1 s of the 4 s, never together
    ([[(0.0, 1.0)], [(1.0, 2.0)], [(2.0, 3.0)], [(3.0, 4.0)]], 25.0),
    # together: every chip busy over the same two intervals
    ([[(0.5, 1.5), (2.0, 3.0)]] * 4, 100.0),
    # two pairs: chips 0-1 together, then 2-3 together
    ([[(0.0, 1.0)], [(0.0, 1.0)], [(1.0, 2.0)], [(1.0, 2.0)]], 50.0),
])
def test_chip_overlap_on_synthetic_traces(layout, want):
    ctx = SimpleNamespace(trace=_chips(layout))
    assert OVERLAP.read(ctx) == pytest.approx(want)


def test_chip_overlap_needs_two_chips_and_some_work():
    assert OVERLAP.read(SimpleNamespace(trace=None)) is None
    assert OVERLAP.read(SimpleNamespace(trace=_chips([[(0.0, 1.0)]]))) is None
    assert OVERLAP.read(SimpleNamespace(trace=_chips([[], []]))) is None


def test_fleet_mfu_divides_by_every_chips_program_time():
    mfu = spec.load_module(bench_fixture.BENCH, "metrics", "mfu.fleet4")
    tr = _chips([[(0.0, 1.0)], [(1.0, 2.0)], [(2.0, 2.5)], [(3.0, 3.5)]])
    calls = [serve.Call("bench.segment", 0.5, 0.6, flops=1.2e12),
             serve.Call("bench.admit", 1.0, 1.1, flops=0.3e12),
             serve.Call("bench.dpu_launch", 1.0, 1.1, ops=5e12),
             serve.Call("bench.segment", 9.0, 9.1, flops=7e12)]
    ctx = SimpleNamespace(trace=tr, calls=calls, peaks={"flops": 1e12},
                          trace_window_host=(0.0, 4.0))
    # 1.5e12 useful FLOPs over 3 s of programs on four chips at 1e12/s
    assert mfu.read(ctx) == pytest.approx(50.0)
    assert mfu.read(SimpleNamespace(trace=_chips([[(0.0, 1.0)]]),
                                    calls=calls)) is None


def test_fleet_idle_share_is_the_mean_over_chips():
    idle = spec.load_module(bench_fixture.BENCH, "metrics",
                            "device_idle_share.fleet4")
    tr = _chips([[(0.0, 4.0)], [(0.0, 2.0)], [(0.0, 1.0)], []])
    # busy 4, 2, 1, 0 of 4 s: idle 0, 50, 75, 100%
    assert idle.read(SimpleNamespace(trace=tr)) == pytest.approx(56.25)


def test_breakdown_names_each_chip_on_a_fleet_and_none_on_one():
    one = _chips([[(0.5, 1.0)]])
    b1 = T.breakdown(one)
    assert b1["device_ops"] == T.top_ops(one.devices[0], one.window)
    assert all("/" in k and not k.startswith("tpu")
               for k, _ in b1["device_ops"])
    four = _chips([[(0.0, 1.0)], [(1.0, 3.0)], [(2.0, 2.5)], [(3.0, 3.5)]])
    b4 = T.breakdown(four)
    assert [k for k, _ in b4["device_ops"]] == [
        "tpu1/jit__segment/fusion.1", "tpu0/jit__segment/fusion.0",
        "tpu2/jit__segment/fusion.2", "tpu3/jit__segment/fusion.3"]
    assert all(k.split("/")[0] in ("tpu0", "tpu1", "tpu2", "tpu3")
               for k, _ in b4["idle_gaps"])
    assert len(b4["idle_gaps"]) <= 10
    assert T.device_busy(four) == pytest.approx((1 + 2 + 0.5 + 0.5) / 4)


def _served(i, n_tokens):
    import numpy as np

    return serve.Served(plan=SimpleNamespace(index=i, prompt=np.zeros(4)),
                        due=0.0, tokens=np.zeros(n_tokens, np.int32),
                        status="completed")


def test_token_sample_tops_up_until_every_slice_has_a_request():
    served = [_served(i, 50 if i < 12 else 5) for i in range(16)]
    slice_of = {i: str(i // 4) for i in range(16)}  # rids 12-15: slice 3
    plain = check.token_sample(served, 7, 60)
    topped = check.token_sample(served, 7, 60, slice_of)
    assert topped[:len(plain)] == plain
    assert {slice_of[s.plan.index] for s in topped} == {"0", "1", "2", "3"}
    assert len(topped) - len(plain) == 4 - len(
        {slice_of[s.plan.index] for s in plain})
    # one engine: no slices named, the sample is as it was
    assert check.token_sample(served, 7, 60, None) == plain


def test_retiring_slice_takes_each_requests_first_retire():
    ev = [SimpleNamespace(kind="retire", rid=1, sid="2"),
          SimpleNamespace(kind="admit", rid=None, sid="0"),
          SimpleNamespace(kind="retire", rid=1, sid="3"),
          SimpleNamespace(kind="retire", rid=4, sid="0")]
    assert check.retiring_slice(ev) == {1: "2", 4: "0"}


def test_fleet_cell_is_in_the_benchmark():
    cell = spec.load_cell("mamba2-370m.voice_fleet4")
    assert cell.chips == 4 and cell.traffic["name"] == "voice_fleet4"
    assert serve.fleet_size(cell.config, cell.chips) == 4
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu.fleet4", "device_idle_share.fleet4",
            "chip_overlap.fleet4"} | set(SPAN_METRICS) == names
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    voice = spec.load_cell("mamba2-370m.voice")
    mix = dict(cell.traffic)
    for k in ("name", "shape_seed", "rate_qps"):
        mix.pop(k)
    for k in ("name", "shape_seed", "rate_qps"):
        voice.traffic.pop(k)
    assert mix == voice.traffic
    assert Path(bench_fixture.BENCH / "traffic" / "voice_fleet4.json").is_file()
