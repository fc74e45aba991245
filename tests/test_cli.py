"""CLI tests (reference console arg-parsing tier + quickstart flow pieces).

Run commands in-process via main(argv) against isolated storage.
"""

import datetime as dt
import json

import pytest

import pio_tpu.templates  # noqa: F401
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.storage import Storage
from pio_tpu.tools.cli import main


@pytest.fixture(autouse=True)
def isolated(tmp_home):
    Storage.reset()
    yield
    Storage.reset()


def run(capsys, *argv):
    code = main(list(argv))
    out = capsys.readouterr()
    return code, out.out, out.err


class TestAppVerbs:
    def test_app_lifecycle(self, capsys):
        code, out, _ = run(capsys, "app", "new", "shop")
        assert code == 0 and "Access key:" in out
        key = out.split("Access key:")[1].strip()

        code, out, _ = run(capsys, "app", "list")
        assert "name=shop" in out and key in out

        code, out, _ = run(capsys, "accesskey", "new", "shop", "--events", "rate,buy")
        assert code == 0

        code, out, _ = run(capsys, "accesskey", "list", "shop")
        assert out.count("key=") == 2 and "events=rate,buy" in out

        code, out, _ = run(capsys, "app", "channel-new", "shop", "mobile")
        assert code == 0

        code, out, err = run(capsys, "app", "channel-new", "shop", "bad name")
        assert code == 1 and "channel" in err

        code, _, _ = run(capsys, "app", "delete", "shop")
        assert code == 0
        code, out, _ = run(capsys, "app", "list")
        assert "shop" not in out

    def test_duplicate_app(self, capsys):
        run(capsys, "app", "new", "shop")
        code, _, err = run(capsys, "app", "new", "shop")
        assert code == 1 and "already exists" in err

    def test_data_delete(self, capsys):
        run(capsys, "app", "new", "shop")
        app = Storage.get_meta_data_apps().get_by_name("shop")
        Storage.get_levents().insert(Event("rate", "user", "u1"), app.id)
        assert len(Storage.get_levents().find(app.id)) == 1
        code, _, _ = run(capsys, "app", "data-delete", "shop")
        assert code == 0
        assert Storage.get_levents().find(app.id) == []


class TestStatusVersion:
    def test_version(self, capsys):
        code, out, _ = run(capsys, "version")
        assert code == 0 and out.strip()

    def test_status(self, capsys):
        code, out, _ = run(capsys, "status")
        assert code == 0
        assert "sanity check passed" in out
        assert out.count("OK ") >= 7
        # where the process runs and where its compiles are kept
        assert "backend: cpu  kinds: cpu  count: 8" in out
        assert "compile cache: " in out

    def test_status_fails_when_backend_cannot_initialise(
        self, capsys, monkeypatch
    ):
        """A backend that fails to initialise (chip held by another
        process) is a FAIL line and exit 1, like a broken store."""
        import jax

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", no_backend)
        code, out, _ = run(capsys, "status")
        assert code == 1
        assert "FAIL devices (Unable to initialize backend 'tpu')" in out
        assert "sanity check FAILED" in out


class TestTrainDeployFlow:
    def _seed(self, capsys, tmp_path):
        run(capsys, "app", "new", "cli-test")
        app = Storage.get_meta_data_apps().get_by_name("cli-test")
        lines = []
        t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
        for u in range(8):
            for i in range(6):
                rating = 5.0 if (u < 4) == (i < 3) else 1.0
                lines.append(json.dumps({
                    "event": "rate", "entityType": "user", "entityId": f"u{u}",
                    "targetEntityType": "item", "targetEntityId": f"i{i}",
                    "properties": {"rating": rating},
                    "eventTime": t0.isoformat(),
                }))
        events_file = tmp_path / "events.jsonl"
        events_file.write_text("\n".join(lines) + "\nnot json\n")
        engine_json = tmp_path / "engine.json"
        engine_json.write_text(json.dumps({
            "id": "cli-rec",
            "engineFactory": "templates.recommendation",
            "datasource": {"params": {"app_name": "cli-test"}},
            "algorithms": [{"name": "als", "params":
                            {"rank": 4, "num_iterations": 6, "lambda_": 0.1}}],
        }))
        return app, events_file, engine_json

    def test_import_train_batchpredict_export(self, capsys, tmp_path):
        app, events_file, engine_json = self._seed(capsys, tmp_path)

        code, out, _ = run(capsys, "import", "--app", "cli-test",
                           "--input", str(events_file))
        assert code == 1  # one bad line
        assert "Imported 48 events (1 failed)" in out

        code, out, _ = run(capsys, "train", "--engine-json", str(engine_json))
        assert code == 0 and "Training completed" in out

        queries = tmp_path / "queries.jsonl"
        queries.write_text(
            json.dumps({"user": "u1", "num": 2}) + "\n"
            + json.dumps({"user": "ghost"}) + "\n"
            + "{bad json\n"
        )
        out_file = tmp_path / "preds.jsonl"
        code, out, _ = run(
            capsys, "batchpredict", "--engine-json", str(engine_json),
            "--input", str(queries), "--output", str(out_file),
        )
        assert code == 0 and "2 queries" in out
        lines = [json.loads(l) for l in out_file.read_text().splitlines()]
        assert len(lines[0]["prediction"]["itemScores"]) == 2
        assert lines[1]["prediction"]["itemScores"] == []
        assert "error" in lines[2]

        export_file = tmp_path / "export.jsonl"
        code, out, _ = run(capsys, "export", "--app", "cli-test",
                           "--output", str(export_file))
        assert code == 0 and "Exported 48" in out
        assert len(export_file.read_text().splitlines()) == 48

    def test_train_stop_after_read(self, capsys, tmp_path):
        app, events_file, engine_json = self._seed(capsys, tmp_path)
        run(capsys, "import", "--app", "cli-test", "--input", str(events_file))
        code, out, _ = run(capsys, "train", "--engine-json", str(engine_json),
                           "--stop-after-read")
        assert code == 0

    def test_train_missing_engine_json(self, capsys):
        with pytest.raises(Exception):
            run(capsys, "train", "--engine-json", "/nope/engine.json")

    def test_undeploy_unreachable(self, capsys):
        code, _, err = run(capsys, "undeploy", "--port", "59999")
        assert code == 1 and "cannot reach" in err


class TestRunVerb:
    def test_run_calls_target_with_args(self, tmp_path, monkeypatch):
        import sys

        mod = tmp_path / "userjob.py"
        mod.write_text(
            "def main(argv):\n"
            "    print('JOB', argv)\n"
            "    return 0 if argv == ['a', 'b'] else 3\n"
            "def noargs():\n"
            "    print('NOARGS')\n"
        )
        monkeypatch.syspath_prepend(str(tmp_path))
        sys.modules.pop("userjob", None)
        from pio_tpu.tools.cli import main

        assert main(["run", "userjob:main", "a", "b"]) == 0
        assert main(["run", "userjob:main", "x"]) == 3
        assert main(["run", "userjob:noargs"]) == 0
        # flag-like passthrough needs no -- separator (REMAINDER)
        assert main(["run", "userjob:main", "--flag", "v"]) == 3
        # args to a no-arg target is an error, not silent discard
        assert main(["run", "userjob:noargs", "oops"]) == 1

    def test_run_rejects_non_callable(self, tmp_path, monkeypatch):
        import sys

        (tmp_path / "userdata.py").write_text("VALUE = 7\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        sys.modules.pop("userdata", None)
        from pio_tpu.tools.cli import main

        assert main(["run", "userdata:VALUE"]) == 1


def test_deploy_workers_flags_parse():
    """`deploy --workers N --device-worker` must parse (the pool branch
    of cmd_deploy keys off these; pool behavior itself is covered by
    tests/test_worker_pool.py)."""
    from pio_tpu.tools.cli import build_parser

    p = build_parser()
    args = p.parse_args(
        ["deploy", "--workers", "4", "--device-worker", "--port", "8123"]
    )
    assert args.workers == 4 and args.device_worker is True
    assert args.port == 8123
    # default stays single-process
    args = p.parse_args(["deploy"])
    assert args.workers == 1 and args.device_worker is False
