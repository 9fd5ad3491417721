"""chip_smoke.py is the proof that the system starts on the chip — so off the chip it
must refuse, loudly and before any model code runs (the full run needs a TPU: the chip
tool runs it, not this suite)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}, timeout=120,
    )
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout           # it says what it found ...
    assert "[train" not in r.stdout             # ... and did no work ...
    assert '"ok"' not in r.stdout               # ... and printed no result.
