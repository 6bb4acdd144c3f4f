"""CLI command tests — driven through the real argparse entry (cli.main)."""

import json

import pytest

from theroundtaible_tpu.adapters.fake import scripted_response
from theroundtaible_tpu.cli import build_parser, main
from theroundtaible_tpu.commands.discuss import get_last_proposals
from theroundtaible_tpu.core.types import ConsensusBlock, RoundEntry


def write_config(project_root, knights=None, rules=None):
    cfg = {
        "version": "1.0", "project": "t", "language": "en",
        "knights": knights or [
            {"name": "A", "adapter": "fake", "capabilities": [],
             "priority": 1}],
        "rules": rules or {
            "max_rounds": 2, "consensus_threshold": 9,
            "timeout_per_turn_seconds": 5, "escalate_to_user_after": 3,
            "auto_execute": False, "ignore": [".git"]},
        "chronicle": "chronicle.md",
        "adapter_config": {"fake": {"name": "A"}},
    }
    (project_root / ".roundtable").mkdir(exist_ok=True)
    (project_root / ".roundtable" / "config.json").write_text(
        json.dumps(cfg))
    return cfg


class TestParser:
    def test_all_commands_registered(self):
        p = build_parser()
        for argv in (["init"], ["discuss", "t"], ["summon"], ["status"],
                     ["list"], ["chronicle"], ["decrees"],
                     ["manifest", "list"], ["apply"], ["code-red", "x"]):
            args = p.parse_args(argv)
            assert args.command == argv[0]

    def test_no_command_prints_help(self, capsys):
        assert main([]) == 0
        assert "roundtable" in capsys.readouterr().out


class TestReadOnlyCommands:
    def test_status_empty(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["status"]) == 0
        assert "No sessions yet" in capsys.readouterr().out

    def test_list_empty(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["list"]) == 0
        assert "No sessions yet" in capsys.readouterr().out

    def test_chronicle_empty(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["chronicle"]) == 0
        assert "chronicle is empty" in capsys.readouterr().out

    def test_decrees_empty(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["decrees"]) == 0
        assert "No decrees yet" in capsys.readouterr().out

    def test_manifest_list_empty(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["manifest", "list"]) == 0
        assert "manifest is empty" in capsys.readouterr().out

    def test_manifest_check_clean(self, project_root, monkeypatch, capsys):
        monkeypatch.chdir(project_root)
        assert main(["manifest", "check"]) == 0
        assert "clean" in capsys.readouterr().out


class TestDiscussCommandE2E:
    def test_full_discuss_reaches_consensus(self, project_root, monkeypatch,
                                            capsys):
        write_config(project_root)
        monkeypatch.chdir(project_root)
        rc = main(["discuss", "Should we do X?", "--no-read-code"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "actually agree" in out
        sessions = list((project_root / ".roundtable" / "sessions").iterdir())
        assert len(sessions) == 1
        assert (sessions[0] / "decisions.md").exists()

    def test_discuss_without_config_exits_config_code(self, tmp_path,
                                                      monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        rc = main(["discuss", "topic", "--no-read-code"])
        assert rc == 2  # ExitCode.CONFIG
        assert "roundtable init" in capsys.readouterr().err

    def test_status_after_discuss(self, project_root, monkeypatch, capsys):
        write_config(project_root)
        monkeypatch.chdir(project_root)
        main(["discuss", "topic one", "--no-read-code"])
        capsys.readouterr()
        assert main(["status"]) == 0
        out = capsys.readouterr().out
        assert "Consensus reached" in out
        assert "topic one" in out
        assert main(["list"]) == 0
        assert "topic one" in capsys.readouterr().out
        assert main(["chronicle"]) == 0
        assert "1 decision(s)" in capsys.readouterr().out


class TestContinueCommand:
    """`discuss --continue` crash resume (ADVICE r1: the path was broken —
    SessionInfo was treated as a path — and unreachable from the CLI)."""

    def test_parser_accepts_continue(self):
        p = build_parser()
        args = p.parse_args(["discuss", "--continue"])
        assert args.continue_session is True
        assert args.topic is None

    def test_parser_rejects_topic_plus_continue(self):
        with pytest.raises(SystemExit):
            build_parser().parse_args(["discuss", "t", "--continue"])

    def test_continue_without_sessions(self, project_root, monkeypatch,
                                       capsys):
        write_config(project_root)
        monkeypatch.chdir(project_root)
        rc = main(["discuss", "--continue", "--no-read-code"])
        assert rc == 1
        assert "No sessions to continue" in capsys.readouterr().out

    def test_continue_resumes_crashed_session(self, project_root,
                                              monkeypatch, capsys):
        from theroundtaible_tpu.utils.session import (
            create_session, update_status, write_transcript)

        write_config(project_root)
        monkeypatch.chdir(project_root)
        # Simulate a crash after round 1: session dir + transcript.json
        # exist, phase still "discussing", no decisions.md.
        sp = create_session(project_root, "an unfinished topic")
        entry = RoundEntry("A", 1, scripted_response(5),
                           ConsensusBlock("A", 1, 5), "ts")
        write_transcript(sp, [entry])
        update_status(sp, phase="discussing", round=1)

        rc = main(["discuss", "--continue", "--no-read-code"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Resuming" in out
        # default FakeAdapter scores 9 → consensus in the resumed round
        assert "actually agree" in out
        assert (sp / "decisions.md").exists()
        # no second session dir was created — same session resumed
        sessions = list((project_root / ".roundtable" / "sessions").iterdir())
        assert len(sessions) == 1

    def test_continue_rejects_finished_session(self, project_root,
                                               monkeypatch, capsys):
        write_config(project_root)
        monkeypatch.chdir(project_root)
        main(["discuss", "done topic", "--no-read-code"])
        capsys.readouterr()
        rc = main(["discuss", "--continue", "--no-read-code"])
        assert rc == 1
        assert "not resumable" in capsys.readouterr().out


class TestWarmupCommand:
    def test_no_tpu_knights_is_noop(self, project_root, monkeypatch,
                                    capsys):
        write_config(project_root)  # fake adapter only
        monkeypatch.chdir(project_root)
        assert main(["warmup"]) == 0
        assert "nothing to warm" in capsys.readouterr().out

    def test_warms_tpu_engine(self, project_root, monkeypatch, capsys):
        import json as _json

        from theroundtaible_tpu.engine import reset_engines

        cfg = {
            "version": "1.0", "project": "t", "language": "en",
            "knights": [
                {"name": "A", "adapter": "tpu-llm", "capabilities": [],
                 "priority": 1},
                {"name": "B", "adapter": "tpu-llm", "capabilities": [],
                 "priority": 2}],
            "rules": {"max_rounds": 1, "consensus_threshold": 9,
                      "timeout_per_turn_seconds": 600,
                      "escalate_to_user_after": 3, "auto_execute": False,
                      "ignore": []},
            "chronicle": "chronicle.md",
            "adapter_config": {"tpu-llm": {
                "model": "tiny-gemma", "max_seq_len": 256, "num_slots": 4,
                "sampling": {"temperature": 0.0, "max_new_tokens": 8}}},
        }
        (project_root / ".roundtable" / "config.json").write_text(
            _json.dumps(cfg))
        monkeypatch.chdir(project_root)
        reset_engines()
        assert main(["warmup"]) == 0
        out = capsys.readouterr().out
        assert "batch sizes [1, 2]" in out
        assert "tiny-gemma" in out
        reset_engines()


class TestGatewayBuildScheduler:
    """`roundtable gateway`'s scheduler seam (ISSUE 22 satellite): a
    seat whose engine cannot be built must say why — an out-of-memory
    on the chip used to surface as a bare "no scheduler available"."""

    def _config(self, project_root, engine_cfg):
        from theroundtaible_tpu.core.config import load_config

        write_config(project_root, knights=[
            {"name": "A", "adapter": "tpu-llm", "capabilities": [],
             "priority": 1}])
        path = project_root / ".roundtable" / "config.json"
        cfg = json.loads(path.read_text())
        cfg["adapter_config"] = {"tpu-llm": engine_cfg}
        path.write_text(json.dumps(cfg))
        return load_config(project_root)

    def test_unbuildable_seat_reports_its_reason(self, project_root):
        from theroundtaible_tpu.commands.gateway_cmd import \
            _build_scheduler
        from theroundtaible_tpu.core.errors import ConfigError
        from theroundtaible_tpu.engine import reset_engines

        reset_engines()
        config = self._config(project_root, {"model": "no-such-model"})
        with pytest.raises(ConfigError) as err:
            _build_scheduler(config, None)
        assert "no scheduler available" in str(err.value)
        assert "no-such-model" in str(err.value)
        reset_engines()

    def test_scheduler_failure_is_chained(self, project_root,
                                          monkeypatch):
        from theroundtaible_tpu.commands.gateway_cmd import \
            _build_scheduler
        from theroundtaible_tpu.core.errors import ConfigError
        from theroundtaible_tpu.engine import reset_engines
        from theroundtaible_tpu.engine import scheduler as sched_mod

        def boom(engine, **opts):
            raise MemoryError("RESOURCE_EXHAUSTED: out of device memory")

        reset_engines()
        monkeypatch.setattr(sched_mod, "acquire_scheduler", boom)
        config = self._config(project_root, {
            "model": "tiny-gemma", "max_seq_len": 256, "num_slots": 2})
        with pytest.raises(ConfigError) as err:
            _build_scheduler(config, None)
        assert isinstance(err.value.__cause__, MemoryError)
        assert "RESOURCE_EXHAUSTED" in str(err.value)
        reset_engines()

    def test_buildable_seat_is_unchanged(self, project_root):
        from theroundtaible_tpu.commands.gateway_cmd import \
            _build_scheduler
        from theroundtaible_tpu.engine import reset_engines

        reset_engines()
        config = self._config(project_root, {
            "model": "tiny-gemma", "max_seq_len": 256, "num_slots": 2,
            "kv_layout": "paged"})
        sched = _build_scheduler(config, None)
        try:
            assert sched.engine.cfg.name == "tiny-gemma"
            assert sched.journal is None
        finally:
            sched.close()
            reset_engines()


class TestAtomicWrites:
    def test_atomic_write_replaces_and_cleans_up(self, tmp_path):
        from theroundtaible_tpu.utils.session import atomic_write_text
        target = tmp_path / "status.json"
        target.write_text("old")
        atomic_write_text(target, "new")
        assert target.read_text() == "new"
        # no stray temp files left behind
        assert [p.name for p in tmp_path.iterdir()] == ["status.json"]


class TestInitCommand:
    def test_non_interactive_scaffold(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        rc = main(["init"])
        assert rc == 0
        cfg_path = tmp_path / ".roundtable" / "config.json"
        assert cfg_path.exists()
        cfg = json.loads(cfg_path.read_text())
        assert cfg["rules"]["max_rounds"] == 5
        assert cfg["rules"]["consensus_threshold"] == 9
        assert (tmp_path / ".roundtable" / "sessions").is_dir()
        assert (tmp_path / ".roundtable" / "manifest.json").exists()
        # chronicle lives INSIDE .roundtable/ (reference init.ts:217,407)
        assert (tmp_path / ".roundtable" / "chronicle.md").exists()
        assert cfg["chronicle"] == ".roundtable/chronicle.md"

    def test_reinit_guard_non_interactive(self, tmp_path, monkeypatch,
                                          capsys):
        monkeypatch.chdir(tmp_path)
        main(["init"])
        before = (tmp_path / ".roundtable" / "config.json").read_text()
        rc = main(["init"])
        assert rc == 0
        assert (tmp_path / ".roundtable" / "config.json").read_text() == before


class TestProposalSummaries:
    def test_get_last_proposals(self):
        rounds = [
            RoundEntry("A", 1, scripted_response(5, text="First analysis "
                                                 "with enough length"),
                       ConsensusBlock("A", 1, 5), "ts"),
            RoundEntry("A", 2, scripted_response(7, text="Second thoughts, "
                                                 "also long enough"),
                       ConsensusBlock("A", 2, 7), "ts"),
            RoundEntry("B", 2, scripted_response(3, text="B disagrees "
                                                 "strongly here"),
                       ConsensusBlock("B", 2, 3), "ts"),
        ]
        proposals = get_last_proposals(rounds)
        assert len(proposals) == 2
        a = next(p for p in proposals if p.knight == "A")
        assert a.score == 7
        assert a.summary.startswith("Second thoughts")
        assert "consensus_score" not in a.summary
