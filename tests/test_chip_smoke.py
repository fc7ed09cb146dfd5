"""chip_smoke.py off the chip: it can never pass here, and its phases are
rehearsed on the CPU at GPTConfig.tiny() by calling them, not through an
option of the script."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))

TINY = chip_smoke.Size(model="tiny", batch=16, seq=128,
                       kernel_shapes=((2, 4, 128, 32), (1, 2, 256, 32)),
                       prompt_len=128,
                       platform="cpu")


@pytest.mark.parametrize("fake_chips", ["", "1"],
                         ids=["no_chip_detected", "chip_resource_faked"])
def test_chip_smoke_cannot_pass_without_a_chip(fake_chips):
    """No TPU resource detected: refused before any lease. A TPU resource
    that is only claimed: the first worker finds JAX on the CPU and fails
    the run — nothing carries on on the CPU."""
    env = dict(os.environ, RAY_TPU_FAKE_TPU_CHIPS=fake_chips)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=150)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert ("detected" if not fake_chips else "'cpu'") in last["error"]


@pytest.fixture
def cluster(request):
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4, num_tpus=request.param)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.mark.timeout(420)
@pytest.mark.parametrize("cluster", [1], indirect=True)
def test_one_chip_phases_rehearsed_on_cpu(cluster):
    device = chip_smoke.one_chip_phases(TINY, seed=0)
    assert device["platform"] == "cpu"


@pytest.mark.timeout(300)
@pytest.mark.parametrize("cluster", [4], indirect=True)
def test_four_chip_phase_rehearsed_on_cpu(cluster):
    device = chip_smoke.four_chip_phase(TINY, seed=0)
    assert device["count"] >= 4
