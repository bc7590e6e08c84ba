"""The limit every test runs under (tests/conftest.py), driven the way
it is used: pytest in a subprocess on a temp file, under a copy of the
conftest."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SPINS = '''
    while True:
        pass
'''

# a loop that is busy and never idle, whose tasks swallow what is
# raised into them, as a daemon's handlers do
BUSY_LOOP = '''
    async def daemon():
        while True:
            try:
                await asyncio.sleep(0)
            except Exception:
                pass

    async def main():
        tasks = [asyncio.ensure_future(daemon()) for _ in range(4)]
        try:
            await asyncio.sleep(3600)
        finally:
            print("main cleaned up after", len(tasks), "daemons")
    asyncio.run(main())
'''

FILE = '''
import asyncio

import pytest


@pytest.mark.time_limit(1)
def test_never_ends():{body}

def test_after():
    pass
'''


@pytest.mark.parametrize("body,stood_in,printed", [
    (SPINS, "in test_never_ends", ""),
    (BUSY_LOOP, "in run_forever", "main cleaned up after 4 daemons"),
], ids=["spins", "busy_loop"])
def test_a_test_past_its_limit_fails_and_the_next_one_runs(
        tmp_path, body, stood_in, printed):
    shutil.copy(Path(__file__).with_name("conftest.py"), tmp_path)
    (tmp_path / "test_cases.py").write_text(FILE.format(body=body))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-o", "markers=time_limit(seconds): limit",
         "-v", "test_cases.py"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    out = proc.stdout
    assert proc.returncode == 1, out + proc.stderr
    assert "test_cases.py::test_never_ends FAILED" in out
    assert "test_cases.py::test_after PASSED" in out
    assert "1 failed, 1 passed" in out
    assert ("TimeLimitExceeded: test_cases.py::test_never_ends ran past "
            "its time limit of 1 s") in out
    # the dump of every thread's stack, on the failing test's stderr
    assert "Current thread" in out and stood_in in out
    # asyncio.run unwound through the test's own finally
    assert printed in out
