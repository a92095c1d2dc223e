"""Example-pipeline integration tests.

Parity: the reference executes every sample notebook end-to-end under
pytest (`tools/notebook/tester/TestNotebooksLocally.py`); here each
baseline example script runs as a subprocess on the virtual CPU mesh.
"""

import os
import subprocess
import sys

import pytest

EXAMPLES = ["drug_discovery_quantile.py", "adult_census_binary.py",
            "cifar10_resnet_scoring.py", "transfer_learning.py",
            "distributed_sgd.py", "text_classification.py",
            "recommender_sar.py", "interpret_lime.py", "serving_demo.py",
            "serving_distributed.py", "flight_delays_regression.py",
            "hyperparam_tuning.py", "opencv_image_pipeline.py",
            "sequence_tagging.py", "multiclass_image_transfer.py"]
EX_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


@pytest.mark.slow
@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(EX_DIR, script)],
                          capture_output=True, text=True, env=env,
                          timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip(), "example printed nothing"
