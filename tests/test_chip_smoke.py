"""chip_smoke.py's contract, as far as a host without a chip can check
it: no accelerator -> non-zero exit and no result line; a failing child
phase -> non-zero exit and no result line, whatever the other phases
did; the parent never imports jax; the CPU rehearsal is only ever what
was asked for and says so in every line."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture()
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json_lines(text):
    out = []
    for ln in text.splitlines():
        if ln.startswith("{"):
            try:
                out.append(json.loads(ln))
            except ValueError:
                pass
    return out


def test_no_accelerator_exits_nonzero_with_no_result():
    """This host has no chip (and the suite pins JAX_PLATFORMS=cpu):
    the plain invocation must fail, not rehearse."""
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not any(doc.get("ok") for doc in _json_lines(proc.stdout))
    assert "not 'tpu'" in proc.stderr and "FAILED" in proc.stderr


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    with open(SCRIPT) as f:
        alone.write_text(f.read())
    proc = subprocess.run([sys.executable, str(alone)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_parent_never_imports_jax():
    code = (
        "import sys, importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('cs', {SCRIPT!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "m._load('multiverso_tpu.client.transport', 'client', "
        "'transport.py')\n"
        "m._load('multiverso_tpu.client.router', 'client', 'router.py')\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "print('JAXFREE')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and "JAXFREE" in proc.stdout, proc.stderr


class _FakeSmoke:
    """Smoke.run() with the phases replaced: which ones 'pass' is the
    test's choice; everything about exit codes and the result line is
    the real code."""

    def __init__(self, smoke, fail, devices=1, lda_state=None):
        self.fail = set(fail)
        self.devices = devices
        # phase -> what its sampler state "hashed to" (default: alike)
        self.lda_state = lda_state or {}
        outer = self

        class Fake(smoke.Smoke):
            def child(self, phase):
                if phase in outer.fail:
                    self.failed.append(f"{phase} (rc=1)")
                    return
                line = {"phase": phase, "ok": True, "platform": "tpu",
                        "device_kind": "TPU v5 lite",
                        "devices": outer.devices, "compile_s": 1.0}
                if self.rehearse:       # the real child tags its line
                    line["rehearsal"] = smoke.REHEARSAL_TAG
                if phase.partition("@")[0] == "lda":
                    line["checked"] = {"sha256": {
                        "assignments": outer.lda_state.get(phase, "a1")}}
                self.lines.append(line)
                print(json.dumps(line))

            def server(self):
                if "server" in outer.fail:
                    raise AssertionError("kv values differ from numpy")
                self.emit({"phase": "server", "ok": True})

            def fleet(self, n):
                if "fleet" in outer.fail:
                    raise RuntimeError("fleet launcher exited rc=1")
                self.emit({"phase": "fleet", "ok": True, "members": n})

        self.cls = Fake


@pytest.mark.parametrize("fail", [
    ["lda"], ["tables"], ["server"], ["w2v"], ["lda", "server"]])
def test_any_failed_phase_fails_the_run(smoke, capsys, fail):
    rc = _FakeSmoke(smoke, fail).cls(rehearse=False).run()
    out = capsys.readouterr()
    assert rc != 0
    assert not any(doc.get("ok") is True and "device" in doc
                   for doc in _json_lines(out.out))
    assert "FAILED" in out.err
    for name in fail[:1]:
        assert name in out.err


def test_four_chip_phases_fail_the_run_too(smoke, capsys):
    for fail in (["fleet"], ["lda@2x2"]):
        rc = _FakeSmoke(smoke, fail, devices=4).cls(rehearse=False).run()
        out = capsys.readouterr()
        assert rc != 0 and "FAILED" in out.err
        assert not any("device" in doc for doc in _json_lines(out.out))


@pytest.mark.parametrize("odd", ["lda", "lda@1x1", "lda@2x2"])
def test_lda_state_that_differs_between_meshes_fails_the_run(smoke, capsys,
                                                             odd):
    """Every ``lda*`` phase passed on its own, and one mesh's sampler
    state hashes unlike the others': which chip samples a block changed
    what it samples."""
    rc = _FakeSmoke(smoke, [], devices=4,
                    lda_state={odd: "b2"}).cls(rehearse=False).run()
    out = capsys.readouterr()
    assert rc != 0 and "lda state differs between meshes" in out.err
    assert not any("device" in doc for doc in _json_lines(out.out))
    for phase in ("lda", "lda@1x1", "lda@2x2"):
        assert f"chip_smoke: {phase} sha256 assignments=" in out.out


def test_all_phases_passing_prints_the_result_line_last(smoke, capsys):
    rc = _FakeSmoke(smoke, []).cls(rehearse=False).run()
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}
    assert {"phase": "four_chips", "skipped": "1 device(s)"} \
        in _json_lines("\n".join(lines))
    rc = _FakeSmoke(smoke, [], devices=4).cls(rehearse=False).run()
    docs = _json_lines(capsys.readouterr().out)
    assert rc == 0 and docs[-1]["device"]["count"] == 4
    assert [d["phase"] for d in docs if "phase" in d] == [
        "w2v", "lda", "tables", "attend", "gdn_recur", "w2v_scatter",
        "server",
        "w2v@2x2", "lda@1x1", "lda@2x2", "tables@2x2", "fleet"]


def test_a_real_child_that_raises_is_a_failed_phase(smoke):
    """Through the real process boundary: a phase that raises in its
    child (here: one that does not exist) is recorded as failed."""
    s = smoke.Smoke(rehearse=True)
    s.child("no_such_phase")
    assert s.failed and "no_such_phase" in s.failed[0]
    assert not s.lines


def test_rehearsal_is_explicit_and_marked(smoke, capsys):
    rc = _FakeSmoke(smoke, []).cls(rehearse=True).run()
    out = capsys.readouterr().out
    assert rc == 0
    docs = _json_lines(out)
    assert all("rehearsal" in d for d in docs)
    assert "rehearsal" in out.splitlines()[0]
    # and its last line is NOT the chip result line
    assert docs[-1] != {"ok": True, "device": docs[-1]["device"]}
