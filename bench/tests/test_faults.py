"""Faults planted under the harness make ``correct`` come out false, and so
does the control: the plain reference in fp8 in the program's place.

Each fault breaks the timed path underneath a CPU run at smoke size with the
device gate lifted: a step that returns its state unchanged; a step that
leaves out half of the batch and takes the mean over the rest; with two
clients on two virtual devices, the exchange between them left out.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from bench import check, reference
from bench.program import Program, seed_key
from bench.spec import Cell, load_module
from bench.tests.conftest import REPO, make_checkout
from bench.tests.test_cells import run_cell, smoke_limits
from bench.tokens import client_batches


def state_unchanged(monkeypatch):
    compile_ = Program.compile

    def compile(self, state):
        step = compile_(self, state)
        return lambda s, b: (s, step(s, b)[1])

    monkeypatch.setattr(Program, "compile", compile)


def half_batch(monkeypatch):
    def half(batch):
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}

    def compile(self, state):
        with jax.set_mesh(self.mesh):
            step = self.step_fn.lower(state, half(self.batches[0])).compile()
        return lambda s, b: step(s, half(b))

    monkeypatch.setattr(Program, "compile", compile)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_a_planted_fault_is_not_correct(tmp_path, no_gate, monkeypatch,
                                        fault):
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc": ("smoke-lm.json", "smoke-stc.json", 1, smoke_limits())})
    fault(monkeypatch)
    res = run_cell(no_gate, root, "smoke-stc", 3000000013)
    assert res["correct"] is False, res["checks"]


TWO_CLIENTS = r"""
import json, sys
sys.path[:0] = [{repo!r}, {repo!r} + "/src"]
import jax
from bench.tests.conftest import make_checkout
from bench.tests.test_cells import run_cell, smoke_limits
from bench import run
run.device_gate = lambda chips: {{"platform": "cpu", "kind": "cpu",
                                  "count": chips}}
if {fault!r}:
    from repro.core.protocols import Codec
    Codec.tree_reduce = (lambda self, msgs, axes, n, mask=None,
                         staleness=None: jax.tree.map(lambda t: t / n, msgs))
root = make_checkout({root!r}, {{"smoke-2c": (
    "smoke-lm.json", "smoke-stc-2c.json", 2, smoke_limits())}})
res = run_cell(run, root, "smoke-2c", 3000000017)
print(json.dumps({{"correct": res["correct"], "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("fault", [False, True],
                         ids=["exchange", "no_exchange"])
def test_two_clients_need_the_exchange(tmp_path, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = TWO_CLIENTS.format(repo=REPO, fault=fault,
                              root=str(tmp_path / "co"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not fault), res["checks"]


@pytest.mark.parametrize("seed", [3, 3000000023])
def test_the_fp8_control_is_not_correct(tmp_path, seed):
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc": ("smoke-lm.json", "smoke-stc.json", 1, smoke_limits())})
    cell = Cell(root, "smoke-stc")
    t, m = cell.traffic, cell.model
    model_ref = load_module(cell.model_ref_path)
    proto_ref = load_module(cell.protocol_ref_path)
    batches = client_batches(seed, 1, 3, t["batch_per_client"], t["seq_len"],
                             m["vocab_size"])
    want = reference.readings(model_ref, proto_ref, m, t, seed_key(seed),
                              batches, 3)
    got = reference.readings(model_ref, proto_ref, m, t, seed_key(seed),
                             batches, 3, precision="fp8")
    correct, checks = check.judge(check.gaps(got, want), cell.limits)
    assert correct is False, checks
