"""kft CLI (kubeflow_tpu/cli.py): the kubectl/kfp-CLI analog. Every
subcommand is driven the way a user would — `run` and `build` in-process
through main(argv), `serve` as a real `python -m kubeflow_tpu` subprocess
answering REST on a bound port."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest
import yaml

from kubeflow_tpu.cli import main

def _pod(command):
    return {"spec": {"containers": [{"command": list(command)}]}}


JOB_OK = {
    "apiVersion": "kubeflow.org/v1",
    "kind": "JAXJob",
    "metadata": {"name": "hello"},
    "spec": {
        "replicaSpecs": {
            "Worker": {
                "replicas": 2,
                "template": _pod(
                    [sys.executable, "-c", "print('step=1 loss=0.5')"]
                ),
            }
        }
    },
}


def _write_yaml(tmp_path, doc, name="m.yaml"):
    p = tmp_path / name
    p.write_text(yaml.safe_dump(doc))
    return str(p)


def test_run_job_success_exit_zero(tmp_path, capsys):
    rc = main(["run", "-f", _write_yaml(tmp_path, JOB_OK), "--timeout", "60"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "job/hello: Succeeded" in out


def test_run_job_failure_exit_nonzero_and_logs(tmp_path, capsys):
    bad = yaml.safe_load(yaml.safe_dump(JOB_OK))
    bad["metadata"]["name"] = "boom"
    bad["spec"]["replicaSpecs"]["Worker"]["replicas"] = 1
    bad["spec"]["replicaSpecs"]["Worker"]["template"] = _pod(
        [sys.executable, "-c", "import sys; print('dying'); sys.exit(3)"]
    )
    bad["spec"]["runPolicy"] = {"backoffLimit": 0}
    rc = main(["run", "-f", _write_yaml(tmp_path, bad), "--timeout", "60"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "job/boom: Failed" in out
    assert "dying" in out  # failure logs streamed without --logs


def test_run_experiment_prints_best(tmp_path, capsys):
    exp = {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": "sweep"},
        "spec": {
            "parameters": [
                {"name": "lr", "type": "double", "min": 0.001, "max": 0.1,
                 "log_scale": True},
            ],
            "objective": {"metric": "loss", "type": "minimize"},
            "algorithm": {"name": "random"},
            "parallel_trial_count": 2,
            "max_trial_count": 4,
            "trial_template": {
                "replicas": {
                    "worker": {
                        "replicas": 1,
                        "command": [
                            sys.executable, "-c",
                            "lr=float('${trialParameters.lr}'); "
                            "print(f'step=1 loss={lr*2}')",
                        ],
                    }
                },
                "run_policy": {"backoff_limit": 0},
            },
        },
    }
    rc = main(["run", "-f", _write_yaml(tmp_path, exp), "--timeout", "120"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "experiment/sweep: trials=4 best=" in out


def test_run_rejects_isvc(tmp_path, capsys):
    isvc = {
        "apiVersion": "serving.kserve.io/v1beta1",
        "kind": "InferenceService",
        "metadata": {"name": "m"},
        "spec": {"predictor": {"model": {"modelFormat": {"name": "bert"}}}},
    }
    rc = main(["run", "-f", _write_yaml(tmp_path, isvc)])
    assert rc == 2
    assert "kft serve" in capsys.readouterr().err


def test_build_resolves_overlay(capsys):
    rc = main(["build", "kubeflow_tpu/examples/manifests/overlays/dev"])
    assert rc == 0
    docs = list(yaml.safe_load_all(capsys.readouterr().out))
    assert docs and all("kind" in d for d in docs)


def test_doctor_reports_backend(capsys):
    rc = main(["doctor"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["backend"] == "cpu"  # the suite's platform, in-process
    assert report["devices"] == 8 and report["device_kind"]


def test_serve_subprocess_answers_rest(tmp_path):
    """`python -m kubeflow_tpu serve -f isvc.yaml` — real process, real
    port, real storage-initializer pull of an xgboost checkpoint."""
    model_src = tmp_path / "src"
    model_src.mkdir()
    (model_src / "model.json").write_text(json.dumps({
        "version": [2, 0, 0],
        "learner": {
            "learner_model_param": {
                "base_score": "0.0", "num_class": "0", "num_feature": "1"},
            "objective": {"name": "reg:squarederror"},
            "gradient_booster": {"model": {
                "trees": [{
                    "split_indices": [0, 0, 0],
                    "split_conditions": [0.5, 1.0, -3.0],
                    "left_children": [1, -1, -1],
                    "right_children": [2, -1, -1],
                    "default_left": [True, False, False],
                    "base_weights": [0.0, 0.0, 0.0],
                    "tree_param": {"num_nodes": "3"},
                }],
                "tree_info": [0],
            }},
        },
    }))
    isvc = {
        "apiVersion": "serving.kserve.io/v1beta1",
        "kind": "InferenceService",
        "metadata": {"name": "gbt"},
        "spec": {"predictor": {"model": {
            "modelFormat": {"name": "xgboost"},
            "storageUri": f"file://{model_src}",
        }}},
    }
    graph = {
        "apiVersion": "serving.kserve.io/v1alpha1",
        "kind": "InferenceGraph",
        "metadata": {"name": "g"},
        "spec": {"nodes": {"root": {
            "routerType": "Sequence",
            "steps": [{"serviceName": "gbt"}],
        }}},
    }
    manifest = tmp_path / "m.yaml"
    manifest.write_text(
        yaml.safe_dump(isvc) + "---\n" + yaml.safe_dump(graph)
    )
    port_file = tmp_path / "port"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubeflow_tpu", "serve",
         "-f", str(manifest),
         "--http-port", "0", "--port-file", str(port_file),
         "--model-dir", str(tmp_path / "mnt")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        # generous: under a full parallel suite on a 1-cpu host, the
        # subprocess's jax import + model load alone can take >60s
        deadline = time.time() + 240
        while not port_file.exists() and time.time() < deadline:
            assert proc.poll() is None, proc.stdout.read()
            time.sleep(0.1)
        assert port_file.exists(), "server never wrote the port file"
        port = int(port_file.read_text())
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/gbt:predict",
            data=json.dumps({"instances": [[0.0], [2.0]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = json.loads(r.read())
        assert body["predictions"] == [1.0, -3.0]
        # the InferenceGraph doc in the same manifest serves too
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/graphs/g:infer",
            data=json.dumps({"instances": [[0.0], [2.0]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = json.loads(r.read())
        assert body["predictions"] == [1.0, -3.0]
    finally:
        proc.terminate()
        proc.wait(10)
