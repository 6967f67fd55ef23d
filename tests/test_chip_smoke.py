"""chip_smoke.py off the chip: its phase functions at toy size on the CPU
with the Pallas kernels interpreted (the first rehearsal of the
on-chip-measurement guide: wrong paths, arguments and control flow show
here and cost no chip time), and its refusal to run where there is no TPU.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke as cs  # noqa: E402


@pytest.fixture()
def interpreted_flash(monkeypatch):
    """On the CPU the model takes ``attention_reference``; steer it through
    the flash kernels, interpreted, as the chip takes them compiled."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops.flash_attention import flash_attention

    monkeypatch.setattr(
        tfm,
        "_causal_attention",
        lambda q, k, v, mesh=None: flash_attention(
            q, k, v, causal=True, interpret=True
        ),
    )


def _run_phase(phase: str) -> None:
    dev = jax.devices()[0]
    clock = cs.CompileClock()
    if phase == "sched":
        cs.sched_phase(cs.TOY, "cpu", clock, dev)
    elif phase == "serve":
        cs.serve_phase(cs.TOY, False, clock, dev)
    elif phase == "train":
        cs.train_phase(cs.TOY, False, clock, dev)
    elif phase == "cluster":
        cs.cluster_phase(cs.TOY, "cpu", clock, dev)
    else:
        # float32: XLA:CPU aborts compiling the bf16 pipeline schedule
        # ("Invalid binary instruction opcode copy"), at the parent too
        cs.multichip_phase(
            dataclasses.replace(cs.TOY, dtype="float32"), clock, jax.devices()
        )


@pytest.mark.parametrize(
    "phase", ["sched", "serve", "train", "cluster", "multichip"]
)
def test_phase_at_toy_size(phase, request, capsys):
    if phase != "multichip":
        # under a mesh the CPU keeps the reference (the partitioner splits
        # it); the kernels' shard_map is compiled in test_chip_compile.py
        request.getfixturevalue("interpreted_flash")
    _run_phase(phase)
    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.splitlines()
        if ln.startswith('{"smoke"')
    ]
    done = [ln for ln in lines if ln["smoke"].split(".")[0] == phase]
    assert done and all(ln.get("ok", True) for ln in done), lines


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "not a TPU" in proc.stderr
