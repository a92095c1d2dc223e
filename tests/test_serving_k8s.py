"""k8s serving fleet: manifests + the entrypoints the pods run.

Parity: the reference ships helm charts that deploy its serving layer
onto k8s (`/root/reference/tools/helm/` — spark-serving chart). Here the
fleet is tools/k8s/*.yaml running ``python -m mmlspark_tpu.serving``;
these tests (a) render-check the manifests and assert they agree with
the entrypoint contract (commands, ports, probe endpoints, coordinator
DNS wiring), and (b) smoke the exact pod commands as local OS processes:
coordinator + two workers serving a persisted model, client failover
when one "pod" dies.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
import requests
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K8S = os.path.join(REPO, "tools", "k8s")


def _load(name):
    with open(os.path.join(K8S, name)) as f:
        return list(yaml.safe_load_all(f))


class TestManifests:
    def test_coordinator_manifest_matches_entrypoint(self):
        dep, svc = _load("serving-coordinator.yaml")
        c = dep["spec"]["template"]["spec"]["containers"][0]
        assert c["command"] == ["python", "-m", "mmlspark_tpu.serving",
                                "coordinator"]
        assert c["readinessProbe"]["httpGet"]["path"] == "/services"
        assert svc["kind"] == "Service"
        assert svc["spec"]["ports"][0]["port"] == 8000
        # the service selector must actually select the deployment pods
        labels = dep["spec"]["template"]["metadata"]["labels"]
        assert all(labels.get(k) == v
                   for k, v in svc["spec"]["selector"].items())

    def test_worker_manifest_matches_entrypoint(self):
        (dep,) = _load("serving-workers.yaml")
        c = dep["spec"]["template"]["spec"]["containers"][0]
        assert c["command"] == ["python", "-m", "mmlspark_tpu.serving",
                                "worker"]
        env = {e["name"]: e for e in c["env"]}
        assert "MODEL_URI" in env
        # coordinator DNS name + port must match the coordinator Service
        _, svc = _load("serving-coordinator.yaml")
        expected = (f"http://{svc['metadata']['name']}:"
                    f"{svc['spec']['ports'][0]['port']}")
        assert env["COORDINATOR_URL"]["value"] == expected
        assert env["POD_IP"]["valueFrom"]["fieldRef"]["fieldPath"] \
            == "status.podIP"
        # readiness must be the drain-aware endpoint (flips 503 while
        # the pod still answers), liveness the bare process probe
        assert c["readinessProbe"]["httpGet"]["path"] == "/readyz"
        assert c["livenessProbe"]["httpGet"]["path"] == "/healthz"


class TestRenderTool:
    def test_render_overrides(self):
        sys.path.insert(0, os.path.join(REPO, "tools", "k8s"))
        try:
            import render
        finally:
            sys.path.pop(0)
        docs = render.render(render.parse_sets([
            "replicas=5", "image=gcr.io/me/tpu:v2",
            "model_uri=gs://me/models/m", "journal_pvc=serving-journal",
            "stale_after=45", "env.REGISTER_INTERVAL=5"]))
        by_role = {d["metadata"]["labels"].get("role"): d
                   for d in docs if d.get("kind") == "Deployment"}
        worker, coord = by_role["worker"], by_role["coordinator"]
        assert worker["spec"]["replicas"] == 5
        wc = worker["spec"]["template"]["spec"]["containers"][0]
        cc = coord["spec"]["template"]["spec"]["containers"][0]
        assert wc["image"] == cc["image"] == "gcr.io/me/tpu:v2"
        env = {e["name"]: e.get("value") for e in wc["env"]}
        assert env["MODEL_URI"] == "gs://me/models/m"
        assert env["REGISTER_INTERVAL"] == "5"
        cenv = {e["name"]: e.get("value") for e in cc["env"]}
        assert cenv["STALE_AFTER"] == "45"
        # journal_pvc wires the WHOLE durable-journal story: the PVC
        # volume, the mount, and a per-pod journal file (replicas must
        # not share one journal)
        assert env["JOURNAL_PATH"] == "/journal/$(POD_NAME).jsonl"
        assert any(e.get("name") == "POD_NAME" and "valueFrom" in e
                   for e in wc["env"])
        assert {"name": "journal", "mountPath": "/journal"} \
            in wc["volumeMounts"]
        vols = worker["spec"]["template"]["spec"]["volumes"]
        assert {"name": "journal", "persistentVolumeClaim":
                {"claimName": "serving-journal"}} in vols
        # untouched defaults survive (the manifests stay source of truth)
        assert env["PORT"] == "8000"
        assert any(e.get("name") == "POD_IP" and "valueFrom" in e
                   for e in wc["env"])

    def test_render_defaults_equal_committed_manifests(self):
        sys.path.insert(0, os.path.join(REPO, "tools", "k8s"))
        try:
            import render
        finally:
            sys.path.pop(0)
        docs = render.render(render.parse_sets([]))
        committed = []
        for fname in render.MANIFESTS:
            with open(os.path.join(REPO, "tools", "k8s", fname)) as f:
                committed.extend(d for d in yaml.safe_load_all(f) if d)
        assert docs == committed


class TestEntrypointFleet:
    @pytest.fixture
    def model_dir(self, tmp_path):
        from mmlspark_tpu.core.dataframe import DataFrame, obj_col
        from mmlspark_tpu.gbdt import GBDTRegressor
        rng = np.random.default_rng(0)
        X = rng.normal(size=(80, 3))
        y = X[:, 0] * 2.0
        df = DataFrame({"features": obj_col(list(X)), "label": y})
        model = GBDTRegressor(num_iterations=3, num_leaves=3,
                              min_data_in_leaf=5).fit(df)
        path = str(tmp_path / "served_model")
        model.save(path)
        return path

    def test_fleet_serves_and_fails_over(self, model_dir):
        env_base = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = []
        try:
            coord = subprocess.Popen(
                [sys.executable, "-m", "mmlspark_tpu.serving",
                 "coordinator"],
                env=dict(env_base, PORT="0"), cwd=REPO,
                stdout=subprocess.PIPE, text=True)
            procs.append(coord)
            line = coord.stdout.readline()
            cport = int(line.rsplit(":", 1)[1])
            coord_url = f"http://127.0.0.1:{cport}"

            for _ in range(2):
                wp = subprocess.Popen(
                    [sys.executable, "-m", "mmlspark_tpu.serving",
                     "worker"],
                    env=dict(env_base, PORT="0", MODEL_URI=model_dir,
                             COORDINATOR_URL=coord_url,
                             POD_IP="127.0.0.1", MAX_LATENCY_MS="1"),
                    cwd=REPO, stdout=subprocess.PIPE, text=True)
                procs.append(wp)
                while True:
                    line = wp.stdout.readline()
                    if not line:   # EOF: worker died before registering
                        raise AssertionError(
                            f"worker exited rc={wp.poll()} before "
                            f"registering")
                    if "registered" in line:
                        break

            from mmlspark_tpu.serving.server import ServingClient
            client = ServingClient(coord_url, timeout=30)
            assert len(client._workers) == 2
            r = client.predict({"features": [1.0, 0.0, 0.0]})
            assert "prediction" in r

            # a worker's /status (the pods' readiness probe) is live
            s = requests.get(
                client._workers[0].rsplit("/", 1)[0] + "/status",
                timeout=10).json()
            assert s["n_requests"] >= 1

            procs[1].send_signal(signal.SIGKILL)   # kill one "pod"
            time.sleep(0.3)
            for i in range(6):
                r = client.predict({"features": [float(i), 0.0, 0.0]})
                assert "prediction" in r           # failover kept serving
        finally:
            for p in procs:
                p.kill()

    def test_journal_survives_worker_restart(self, model_dir, tmp_path):
        """Exactly-once across a pod crash-restart: a committed reply
        must REPLAY (not re-execute) when the client retry lands on the
        restarted worker — the durable-journal path the k8s manifests
        enable via JOURNAL_PATH on a PVC mount."""
        env_base = dict(os.environ, JAX_PLATFORMS="cpu")
        jpath = str(tmp_path / "journal" / "worker-0.jsonl")

        def spawn_worker():
            wp = subprocess.Popen(
                [sys.executable, "-m", "mmlspark_tpu.serving", "worker"],
                env=dict(env_base, PORT="0", MODEL_URI=model_dir,
                         MAX_LATENCY_MS="1", JOURNAL_PATH=jpath),
                cwd=REPO, stdout=subprocess.PIPE, text=True)
            line = wp.stdout.readline()
            if not line:
                raise AssertionError(f"worker exited rc={wp.poll()}")
            port = int(line.strip().rsplit(":", 1)[1])
            return wp, f"http://127.0.0.1:{port}"

        wp, base = spawn_worker()
        try:
            rid = "rid-restart-1"
            r1 = requests.post(base + "/predict",
                               json={"features": [1.0, 0.0, 0.0]},
                               headers={"X-Request-Id": rid}, timeout=30)
            assert r1.status_code == 200
            assert "X-Replayed" not in r1.headers

            wp.send_signal(signal.SIGKILL)         # pod crash
            wp.wait(timeout=10)
            wp, base = spawn_worker()              # k8s restarts it

            s = requests.get(base + "/status", timeout=10).json()
            assert s["journal_recovered"] >= 1
            assert s["journal_path"] == jpath

            # the retry spanning the restart replays the committed body
            r2 = requests.post(base + "/predict",
                               json={"features": [1.0, 0.0, 0.0]},
                               headers={"X-Request-Id": rid}, timeout=30)
            assert r2.status_code == 200
            assert r2.headers.get("X-Replayed") == "1"
            assert r2.content == r1.content
        finally:
            wp.kill()
