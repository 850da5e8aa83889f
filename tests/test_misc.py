"""Hub, mounts, render, profiler, notifications tests."""

import mlrun_tpu


def test_hub_import_and_run():
    fn = mlrun_tpu.import_function("hub://iris_trainer")
    assert fn.kind == "job"
    assert fn.spec.default_handler == "trainer"
    run = fn.run(local=True, params={"max_iter": 120})
    assert run.state() == "completed", run.status.error
    assert run.status.results["accuracy"] > 0.8


def test_hub_tpujob_function():
    fn = mlrun_tpu.import_function("hub://llama_finetune")
    assert fn.kind == "tpujob"
    assert fn.spec.topology == "2x4"


def test_mount_modifiers():
    from mlrun_tpu.platforms import mount_gcs_key, mount_pvc, mount_tmpfs

    fn = mlrun_tpu.new_function("m", kind="job", image="x")
    fn.apply(mount_pvc("my-pvc", volume_mount_path="/data"))
    fn.apply(mount_gcs_key())
    fn.apply(mount_tmpfs("2Gi"))
    volumes = {v["name"] for v in fn.spec.volumes}
    assert volumes == {"pvc", "gcs-key", "shm"}
    assert fn.get_env("GOOGLE_APPLICATION_CREDENTIALS") == \
        "/var/secrets/gcs/key.json"
    pod = fn.to_pod_spec()
    assert len(pod["volumes"]) == 3
    assert len(pod["containers"][0]["volumeMounts"]) == 3


def test_render_html():
    from mlrun_tpu.render import artifacts_to_html, runs_to_html

    runs = [{"metadata": {"uid": "abc123", "name": "r"},
             "status": {"state": "completed",
                        "results": {"acc": 0.91234567}}}]
    html = runs_to_html(runs, display=False)
    assert "abc123" in html and "completed" in html and "0.9123" in html
    html2 = artifacts_to_html(
        [{"kind": "model", "metadata": {"key": "m1", "tag": "v1"},
          "spec": {"target_path": "/x"}}], display=False)
    assert "m1" in html2


def test_memory_report():
    from mlrun_tpu.utils.profiler import memory_report

    report = memory_report()
    assert "host_vmrss" in report


def test_console_notification_on_run(capsys):
    def handler(context):
        context.log_result("ok", 1)

    fn = mlrun_tpu.new_function("n", kind="local", handler=handler)
    run = fn.run(local=True, notifications=[
        {"kind": "console", "when": ["completed"],
         "message": "run finished fine"}])
    captured = capsys.readouterr()
    assert "run finished fine" in captured.out
    assert run.state() == "completed"


def test_secrets_store():
    from mlrun_tpu.secrets import SecretsStore

    store = SecretsStore()
    store.add_source("inline", {"API_KEY": "s3cret"})
    assert store.get("API_KEY") == "s3cret"
    # inline secrets are redacted on serialization
    assert store.to_serial() == []


def test_git_notification(monkeypatch):
    """Reference: mlrun/utils/notifications/notification/git.py — comment
    payloads for github and gitlab issue endpoints."""
    import requests as requests_mod

    from mlrun_tpu.utils.notifications.notification import GitNotification

    calls = []

    def fake_post(url, json=None, headers=None, timeout=None):
        calls.append({"url": url, "json": json, "headers": headers})

        class _Resp:
            def raise_for_status(self):
                pass

        return _Resp()

    monkeypatch.setattr(requests_mod, "post", fake_post)

    GitNotification("done", params={
        "repo": "org/repo", "issue": "7", "token": "tkn"}).push(
        "run finished", severity="completed")
    assert calls[0]["url"] == (
        "https://api.github.com/repos/org/repo/issues/7/comments")
    assert calls[0]["headers"]["Authorization"] == "token tkn"
    assert "[completed] run finished" in calls[0]["json"]["body"]

    GitNotification("done", params={
        "repo": "grp/proj", "issue": "3", "token": "tkn",
        "gitlab": True}).push("mr done")
    assert calls[1]["url"] == (
        "https://gitlab.com/api/v4/projects/grp%2Fproj/issues/3/notes")
    assert calls[1]["headers"]["PRIVATE-TOKEN"] == "tkn"

    # GitHub Enterprise serves the API under /api/v3 on the instance host;
    # a self-hosted server requires an explicit provider (hostname
    # inference would misroute a custom-domain GitLab to the GitHub shape)
    GitNotification("done", params={
        "repo": "org/repo", "issue": "9", "token": "tkn",
        "provider": "github", "server": "github.mycompany.com"}).push(
        "ghe done")
    assert calls[2]["url"] == (
        "https://github.mycompany.com/api/v3/repos/org/repo/issues/9/"
        "comments")

    GitNotification("done", params={
        "repo": "grp/proj", "issue": "4", "token": "tkn",
        "provider": "gitlab", "server": "git.mycompany.com"}).push(
        "self-hosted gitlab")
    assert calls[3]["url"] == (
        "https://git.mycompany.com/api/v4/projects/grp%2Fproj/issues/4/"
        "notes")
    assert calls[3]["headers"]["PRIVATE-TOKEN"] == "tkn"

    import pytest as _pytest

    with _pytest.raises(ValueError, match="provider"):
        GitNotification("x", params={
            "repo": "o/r", "issue": "1", "token": "t",
            "server": "git.mycompany.com"}).push("ambiguous server")

    with _pytest.raises(ValueError, match="repo"):
        GitNotification("x", params={}).push("no params")


def test_snowflake_source_gated(monkeypatch):
    """Connection-kwargs builder is testable without the connector; the
    read path raises a clear gate error (reference sources.py:737)."""
    import sys

    import pytest as _pytest

    from mlrun_tpu.datastore import SnowflakeSource
    from mlrun_tpu.datastore.sources import get_source_from_dict

    source = SnowflakeSource(
        "sf", path="DB.SCHEMA.TBL",
        attributes={"account": "acc", "user": "u", "warehouse": "wh",
                    "database": "db", "schema": "sch", "query": "SELECT 1"})
    monkeypatch.setenv("SNOWFLAKE_PASSWORD", "pw")
    assert source.connection_kwargs() == {
        "account": "acc", "user": "u", "warehouse": "wh",
        "database": "db", "schema": "sch", "password": "pw"}
    # serialization round-trips through the kind registry
    again = get_source_from_dict(source.to_dict())
    assert isinstance(again, SnowflakeSource)
    assert again.attributes["account"] == "acc"
    # block the import even where the connector happens to be installed
    monkeypatch.setitem(sys.modules, "snowflake", None)
    monkeypatch.setitem(sys.modules, "snowflake.connector", None)
    with _pytest.raises(ImportError):
        source.to_dataframe()


def test_hub_batch_inference_end_to_end(tmp_path):
    """hub://batch_inference: pickle model + csv in, prediction set +
    accuracy out."""
    import pickle

    import numpy as np
    import pandas as pd
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(0)
    X = rng.normal(size=(80, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    model = LogisticRegression().fit(X, y)
    model_path = tmp_path / "model.pkl"
    model_path.write_bytes(pickle.dumps(model))
    df = pd.DataFrame(X, columns=["a", "b", "c"])
    df["label"] = y
    data_path = tmp_path / "data.csv"
    df.to_csv(data_path, index=False)

    fn = mlrun_tpu.import_function("hub://batch_inference")
    run = fn.run(local=True,
                 inputs={"dataset": str(data_path)},
                 params={"model_path": str(model_path),
                         "label_column": "label"})
    assert run.state() == "completed", run.status.error
    assert run.status.results["prediction_count"] == 80
    assert run.status.results["accuracy"] > 0.9
    assert "prediction_set" in run.status.artifact_uris


def test_hub_describe_end_to_end(tmp_path):
    """hub://describe: stats + histograms + label balance artifacts."""
    import numpy as np
    import pandas as pd

    df = pd.DataFrame({"x": np.arange(50, dtype=float),
                       "cat": (["a"] * 30 + ["b"] * 20)})
    path = tmp_path / "d.csv"
    df.to_csv(path, index=False)
    fn = mlrun_tpu.import_function("hub://describe")
    run = fn.run(local=True, inputs={"dataset": str(path)},
                 params={"label_column": "cat", "bins": 5})
    assert run.state() == "completed", run.status.error
    assert run.status.results["rows"] == 50
    for key in ("summary_stats", "histograms", "label_balance"):
        assert key in run.status.artifact_uris
    import json

    from mlrun_tpu.datastore import store_manager

    db = mlrun_tpu.db.get_run_db()
    art = db.read_artifact("histograms", project=run.metadata.project)
    body = store_manager.object(url=art["spec"]["target_path"]).get()
    hist = json.loads(body)
    assert sum(hist["x"]["counts"]) == 50


def test_hub_drift_analysis(tmp_path):
    """hub://drift_analysis: per-feature drift table + overall status."""
    import numpy as np
    import pandas as pd

    import mlrun_tpu

    rng = np.random.default_rng(0)
    ref = tmp_path / "ref.csv"
    cur = tmp_path / "cur.csv"
    pd.DataFrame({"a": rng.normal(0, 1, 600),
                  "b": rng.normal(0, 1, 600)}).to_csv(ref, index=False)
    pd.DataFrame({"a": rng.normal(0, 1, 600),       # unchanged
                  "b": rng.normal(4, 1, 600)}).to_csv(cur, index=False)

    fn = mlrun_tpu.import_function("hub://drift_analysis")
    run = fn.run(inputs={"sample_set": str(cur),
                         "reference_set": str(ref)}, local=True)
    assert run.state() == "completed", run.status.error
    assert run.status.results["drift_status"] == "DRIFT_DETECTED"
    assert run.status.results["drifted_features"] >= 1
    table = run.artifact("drift_table").as_df()
    verdicts = dict(zip(table["feature"], table["verdict"]))
    assert verdicts["b"] == "DRIFT_DETECTED"
    assert verdicts["a"] == "NO_DRIFT"


def test_hub_model_server(tmp_path):
    """hub://model_server: generic serving router import + mock serve."""
    import pickle

    import numpy as np
    from sklearn.linear_model import LogisticRegression

    import mlrun_tpu

    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 3))
    y = (x.sum(axis=1) > 0).astype(int)
    model_file = tmp_path / "clf.pkl"
    model_file.write_bytes(pickle.dumps(LogisticRegression().fit(x, y)))

    fn = mlrun_tpu.import_function("hub://model_server")
    assert fn.kind == "serving"
    fn.add_model(
        "clf",
        class_name="mlrun_tpu.frameworks.sklearn.SKLearnModelServer",
        model_path=str(model_file))
    server = fn.to_mock_server()
    out = server.test("/v2/models/clf/infer",
                      body={"inputs": x[:4].tolist()})
    assert len(out["outputs"]) == 4
