"""Every shipped example must run end-to-end (≙ the reference's
example/ families being kept working by its integration specs).

Each example runs as a subprocess on the 8-virtual-device CPU backend
with one epoch and a small batch; rc=0 is the contract.  PYTHONPATH is
cleared so each example finds the package the way its docstring says
(through examples/_common.py), matching how examples document CPU runs.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")

# (script, extra args, timeout_s)
CASES = [
    ("lenet.py", ["--epochs", "1", "--batch", "64"], 300),
    ("autoencoder_mnist.py", ["--epochs", "1", "--batch", "64"], 300),
    ("keras_mnist.py", ["--epochs", "1", "--batch", "64"], 300),
    ("resnet_cifar.py", ["--epochs", "1", "--batch", "32"], 420),
    ("rnn_lm.py", ["--epochs", "1", "--batch", "16"], 300),
    ("textclassifier.py", ["--epochs", "1", "--batch", "32"], 300),
    # 1 epoch lands just under the example's own >0.8 accuracy assert
    ("treelstm_sentiment.py", ["--epochs", "3", "--batch", "16"], 300),
    ("serving_predictor.py", ["--batch", "16"], 300),
    ("dlframes_pipeline.py", ["--epochs", "1", "--batch", "32"], 300),
    ("loadmodel.py", [], 420),
    ("distributed_resnet.py", ["--epochs", "1", "--batch", "32"], 600),
    ("transformer_spmd.py", ["--epochs", "1", "--batch", "8"], 600),
    ("textgen.py", ["--epochs", "30"], 300),
    ("control_flow.py", ["--epochs", "8"], 300),
    ("padded_rnn.py", ["--epochs", "6", "--batch", "64"], 300),
    ("imageframe_validation.py", ["--epochs", "4", "--batch", "32"], 300),
]


@pytest.mark.parametrize("script,args,timeout",
                         CASES, ids=[c[0] for c in CASES])
def test_example_runs(script, args, timeout):
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, script)] + args,
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=EXAMPLES_DIR)
    assert proc.returncode == 0, (
        f"{script} failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}")
