"""Entry points report failure through their exit code, and place JAX's
persistent compilation cache where a later run can find it."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import compile_cache

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_serve_lda_dryrun_failure_exits_nonzero(monkeypatch, capsys):
    from repro.launch import serve_lda
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(serve_lda, "run_serve_dryrun",
                        lambda **_: {"ok": False, "error": "lowering broke"})
    monkeypatch.setattr(sys, "argv", ["serve_lda", "--dryrun"])
    with pytest.raises(SystemExit) as exc:
        serve_lda.main()
    assert exc.value.code not in (0, None)
    assert "[FAIL] lda-serve: lowering broke" in capsys.readouterr().out


# dryrun_lda forces 512 host devices when imported, so it runs in a child
# (CPU only) rather than in the test process
_DRYRUN_FAIL = textwrap.dedent("""
    import sys
    sys.argv = ["dryrun_lda", "--mode", "ivi"]
    from repro.launch import dryrun_lda
    dryrun_lda.run_ivi = lambda batch: {"ok": False, "error": "lowering broke"}
    dryrun_lda.main()
""")


def test_dryrun_lda_failure_exits_nonzero():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _DRYRUN_FAIL], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "[FAIL] lda-ivi: lowering broke" in out.stdout, out.stderr[-2000:]
    assert out.returncode != 0


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_honours_environment(monkeypatch, tmp_path,
                                           cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir is None
