import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_script(argv, devices=1, cwd=ROOT, timeout=600):
    """Run a python script on the CPU with ``devices`` host devices; return
    ``(returncode, stdout, stderr)``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}")
    p = subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout, p.stderr


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture
def no_compile_cache(monkeypatch):
    """In-process runs keep JAX's persistent compilation cache off."""
    import repro.util
    monkeypatch.setattr(repro.util, "enable_compile_cache", lambda: None)
