"""chip_smoke.py rehearsed end to end on the CPU, and the rule that decides
which process of a node may open the chip."""

import json
import os
import subprocess
import sys
import types

from ray_tpu.core import accelerator
from ray_tpu.core.nodelet import idle_worker_for

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the test-only argument of chip_smoke.main(): TransformerConfig.tiny, and
# a node whose `TPU` is a stand-in token (JAX_PLATFORMS=cpu finds no chip)
_REHEARSAL = {
    "model": {"tiny": True, "seq": 128},
    # tiny's untied, unscaled head starts 0.55 over ln 256
    "train": {"batch": 2, "steps": 5, "lr": 1e-2, "first_loss_tol": 1.0},
    "serve": {"prompt_lens": (8, 20, 37, 64), "new_tokens": 8,
              "max_len": 128, "logit_gap": 0.1},
    "init_kwargs": {"num_cpus": 4, "resources": {
        "TPU": 1.0, "accelerator_type:rehearsal": 1.0}},
}


def test_rehearsal_walks_every_phase_and_fails_without_a_chip():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         f"sys.exit(chip_smoke.main([], rehearsal={_REHEARSAL!r}))"],
        cwd=_REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=_REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    by_phase = {line["phase"]: line for line in lines if "phase" in line}
    assert [line["phase"] for line in lines if "phase" in line] == [
        "runtime", "train", "handover", "serve_reference", "serve",
        "control_plane", "shutdown", "total"], out.stdout + out.stderr[-3000:]

    # the run as a whole fails, and says why: no chip, no kernel
    assert out.returncode != 0
    assert not any(line.get("ok") for line in lines)
    assert "FAILED (device checks)" in out.stderr
    assert "the training worker ran on" in out.stderr
    assert "the replica ran on" in out.stderr
    assert "holds no tpu_custom_call" in out.stderr

    # every check that needs no device held on the way
    train, serve = by_phase["train"], by_phase["serve"]
    assert len(train["steps"]) == 5 and train["has_kernel"] is False
    assert train["steps"][-1]["loss"] < train["steps"][0]["loss"]
    assert by_phase["serve_reference"]["worst_logit_gap"] <= 0.1
    assert by_phase["serve_reference"]["equals_generate_share"] == 1.0
    assert serve["prompt_lens"] == [8, 20, 37, 64]
    # the decode step and ONE chunk width: prompts of 8-64 tokens, whole
    # chunks and padded remainders alike, a session's alone or, where the
    # prompts prefill together, up to four sessions' in the lanes program
    # (with the insert into a lane and the gather out of one)
    assert len(serve["program_shapes"]) == serve["distinct_program_shapes"]
    assert {"decode_step", "prefill_chunk"} <= {
        s.split(":")[0] for s in serve["program_shapes"]} <= {
        "decode_step", "prefill_chunk", "lane_insert", "lane_gather"}
    assert len({s.split("x")[-1] for s in serve["program_shapes"]
                if s.startswith("prefill_chunk:")}) == 1
    # the hand-over: the training worker's process was gone before the
    # replica started, and the replica is another process
    assert by_phase["handover"]["pid"] == train["pid"] != serve["pid"]
    assert train["device"]["platform"] == serve["device"]["platform"] == "cpu"


def test_chip_smoke_refuses_the_interpreter_switch():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, RAY_TPU_PALLAS_INTERPRET="1"))
    assert out.returncode != 0 and '"ok"' not in out.stdout
    assert "RAY_TPU_PALLAS_INTERPRET" in out.stderr


def test_chip_smoke_fails_on_a_node_without_a_chip():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and '"ok"' not in out.stdout
    assert "the node advertises" in out.stderr


def test_only_a_tpu_reservation_gets_the_tpu_platform():
    tpu, cpu = accelerator.TPU, accelerator.CPU
    # on a node with chips: the reservation, by its own name or a
    # placement group's shadow names, and nothing else
    assert accelerator.reserved_platform({}) == tpu
    assert accelerator.reserved_platform({"JAX_PLATFORMS": "tpu"}) == tpu
    for res in ({"TPU": 1.0}, {"CPU": 1.0, "TPU": 4.0},
                {"TPU_group_0_ab12": 1.0}, {"TPU_group_ab12": 1.0}):
        assert accelerator.worker_platform(res, tpu) == tpu
    for res in ({}, {"CPU": 1.0}, {"TPU": 0.0}, {"GPU": 1.0},
                {"accelerator_type:TPU-v5-lite": 1.0},
                {"CPU_group_0_ab12": 1.0}):
        assert accelerator.worker_platform(res, tpu) == cpu
    # a node held to the CPU is a CPU node throughout
    assert accelerator.reserved_platform({"JAX_PLATFORMS": "cpu"}) == cpu
    assert accelerator.worker_platform({"TPU": 1.0}, cpu) == cpu
    # detection: off on a CPU node, and an expected chip that cannot be
    # opened is an error, not a node without TPU
    assert accelerator.detect_tpu_resources(
        {"JAX_PLATFORMS": "cpu"}, timeout_s=1) == {}
    assert accelerator.expects_tpu({"JAX_PLATFORMS": "tpu"}) is True
    assert accelerator.expects_tpu({}) is None


def test_a_worker_started_on_the_other_platform_is_not_reused():
    def worker(platform, state="idle", lang="py"):
        return types.SimpleNamespace(platform=platform, state=state,
                                     lang=lang)
    pool = [worker("cpu"), worker("tpu", state="leased")]
    assert idle_worker_for(pool, "py", "cpu") is pool[0]
    # the idle CPU worker may have initialised JAX on the CPU: work with
    # a TPU reservation gets a fresh process instead
    assert idle_worker_for(pool, "py", "tpu") is None
    pool[1].state = "idle"
    assert idle_worker_for(pool, "py", "tpu") is pool[1]
    assert idle_worker_for(pool, "cpp", "tpu") is None
