"""The four-chip cell `q8_mesh4`, rehearsed, and its four readers.

The rehearsal needs four devices, and this directory's `conftest.py`
leaves the CPU backend at its one: each rehearsal is a process of its
own with four forced host devices (`--rehearse`: tiny sizes, the CPU, no
look for a chip; nothing it prints is a device number). The sound run
has to come out correct with every kernel sharded, both controls not
correct.

Each reader is held against a synthetic `record` with a known answer,
and has to say `None` where its keys are missing: what a program from
before the names existed (the parent of the PR that added them) gives.
"""

import json
import os
import subprocess
import sys

import pytest

import run

ROOT = os.path.dirname(run.HERE)


def rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "q8_mesh4", "--seed", "3000000027", "--seconds", "3",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [ln for ln in done.stdout.splitlines() if ln]
    return json.loads(lines[-1]), lines


def test_sound_run_is_correct_and_sharded():
    result, lines = rehearse("--trace", "1")
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 4, "memory_peak_bytes": 0}
    # the CPU trace has no device plane: shard_busy_skew is left out
    assert set(result["metrics"]) >= {"exchange_route_share",
                                      "exchange_pad_factor",
                                      "shard_rows_skew"}
    assert "shard_busy_skew" not in result["metrics"]
    assert 1.0 <= result["metrics"]["shard_rows_skew"]["value"] < 4.0
    assert 1.0 <= result["metrics"]["exchange_pad_factor"]["value"] <= 64.0
    assert any("FALLBACK rewrites 0 (limit 0)" in ln for ln in lines)
    # both GROUP BYs traced their sharded steps (the join's traces may
    # fall between two looks of the harness's watch; tier-1's
    # tests/test_mesh_q8.py holds every kernel to being sharded)
    traced = " ".join(ln for ln in lines if "kernel (re)traces" in ln)
    assert "'parallel_agg.step'" in traced
    assert "'parallel_agg.step_fused'" in traced


@pytest.mark.parametrize("control,failed", [("rare_checkpoint", True),
                                            ("short_reference", False)])
def test_controls_are_not_correct(control, failed):
    result, lines = rehearse("--trace", "0", "--control", control)
    assert result["correct"] is False
    assert (result["failed"] >= 1) is failed
    if control == "short_reference":
        assert any("off the reference's by 4096 (limit 0)" in ln
                   for ln in lines)


# -- the readers, against records with known answers ---------------------------


def record(history=None, phase_seconds=None, trace=None, wall_s=20.0):
    return {"window": {"wall_s": wall_s}, "history": history or {},
            "phase_seconds": phase_seconds or {}, "trace": trace}


def read(name, rec):
    return run.load_module("layer_metrics", name).read(rec)


def test_exchange_route_share():
    assert read("exchange_route_share",
                record(phase_seconds={"exchange_route": 0.5,
                                      "host_pack": 3.0})) == 2.5
    assert read("exchange_route_share",
                record(phase_seconds={"host_pack": 3.0})) is None


def test_exchange_pad_factor():
    history = {1: {"mesh_exchange.slots_carried": 131072.0,
                   "mesh_exchange.rows_routed": 30000.0},
               2: {"mesh_exchange.slots_carried": 65536.0,
                   "mesh_exchange.rows_routed": 19152.0},
               3: {"source_rows": 32768.0}}
    assert read("exchange_pad_factor", record(history)) == 4.0
    assert read("exchange_pad_factor",
                record({1: {"source_rows": 32768.0}})) is None
    assert read("exchange_pad_factor", record()) is None


def test_shard_rows_skew():
    history = {1: {"mesh_exchange.shard_rows.0": 100.0,
                   "mesh_exchange.shard_rows.1": 300.0,
                   "mesh_exchange.shard_rows.2": 100.0,
                   "mesh_exchange.shard_rows.3": 100.0},
               2: {"mesh_exchange.shard_rows.0": 300.0,
                   "mesh_exchange.shard_rows.1": 100.0,
                   "mesh_exchange.shard_rows.2": 100.0,
                   "mesh_exchange.shard_rows.3": 100.0,
                   "mesh_exchange.rows_max_shard": 300.0}}
    # per shard over the window 400, 400, 200, 200: the fullest holds
    # 400 of a mean of 300, though each epoch's fullest held 300 of 150
    assert read("shard_rows_skew", record(history)) == 400.0 / 300.0
    assert read("shard_rows_skew",
                record({1: {"source_rows": 32768.0}})) is None


def test_shard_busy_skew():
    per_device = {"/device:TPU:0": 0.30, "/device:TPU:1": 0.20,
                  "/device:TPU:2": 0.25, "/device:TPU:3": 0.25}
    assert read("shard_busy_skew",
                record(trace={"per_device": per_device})) == 1.2
    assert read("shard_busy_skew", record(
        trace={"per_device": {"/device:TPU:0": 0.3}})) is None
    assert read("shard_busy_skew", record(trace={})) is None
    assert read("shard_busy_skew", record(trace=None)) is None
