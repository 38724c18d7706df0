"""Suite-size ratchet: the satellite test additions stay locked in.

CI's coverage gate (``pytest --cov=repro --cov-fail-under=...``) only runs
where ``pytest-cov`` is installable; this container cannot install it, so
the always-on floor is the collected-test count — deleting or breaking the
collection of any suite (e.g. the property-parity or golden-embedding
files) fails tier-1 everywhere, not just in CI.

Raise ``FLOOR`` when tests are added; never lower it to make a PR pass.
"""
import os
import re
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# 485 collected as of the fault-tolerance PR (deadlines, retry/failover,
# circuit breaking, chaos fault model); small slack so a legitimate
# parametrization tweak is not a CI incident
FLOOR = 647


def test_collected_test_count_never_regresses():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q", "tests"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"collection failed:\n{proc.stdout}\n{proc.stderr}"
    m = re.search(r"(\d+)\s+tests?\s+collected", proc.stdout)
    assert m, f"could not parse collection summary:\n{proc.stdout[-2000:]}"
    n = int(m.group(1))
    assert n >= FLOOR, \
        f"collected {n} tests < floor {FLOOR}: a suite was lost or broken"
