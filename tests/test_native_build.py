"""The C parser's on-demand build: safe under processes that build at
once into an empty directory, and quiet where there is no compiler."""

from __future__ import annotations

import fnmatch
import logging
import os
import shutil
import subprocess
import sys

import pytest

from zipkin_tpu import native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = (
    "import sys; from zipkin_tpu import native; "
    "native._BUILD_DIR = sys.argv[1]; print(native.available())"
)


@pytest.mark.skipif(
    not any(shutil.which(cc) for cc in ("cc", "gcc", "clang")),
    reason="no C compiler on PATH",
)
def test_processes_building_at_once_all_load_the_parser(tmp_path):
    for round_ in range(3):
        build_dir = tmp_path / f"round{round_}"
        build_dir.mkdir()
        children = [
            subprocess.Popen(
                [sys.executable, "-c", _CHILD, str(build_dir)],
                cwd=_REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
            for _ in range(6)
        ]
        results = [c.communicate(timeout=60) for c in children]
        assert [out.strip() for out, _ in results] == ["True"] * 6, results
        left = os.listdir(build_dir)
        assert len(left) == 1, left
        assert fnmatch.fnmatch(left[0], "span_json-*.so"), left


def test_no_compiler_degrades_and_leaves_nothing(tmp_path, monkeypatch, caplog):
    def no_such_compiler(cmd, **kwargs):
        raise FileNotFoundError(cmd[0])

    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path))
    monkeypatch.setattr(native.subprocess, "run", no_such_compiler)
    with caplog.at_level(logging.WARNING, logger=native.logger.name):
        assert native._compile() is None
    assert "no C compiler found" in caplog.text
    assert os.listdir(tmp_path) == []
