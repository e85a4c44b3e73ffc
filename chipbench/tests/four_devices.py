"""The slab cell's path on four virtual CPU devices, run in a child
process by ``test_chipbench_faults.py`` (the device count must be set
before JAX starts).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src:. python chipbench/tests/four_devices.py <out.json>

Writes, for the sound program and with the exchange between chips left
out, the plan and the result of one tiny run.
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

import pytest

from chipbench.tests import faults, helpers


def main(out_path: str) -> int:
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    report = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = helpers.tiny_root(Path(tmp))
        for name in ("sound", "exchange_left_out"):
            mp = pytest.MonkeyPatch()
            try:
                helpers.patch_for_cpu(mp)
                if name != "sound":
                    faults.plant(mp, name)
                rc, text, res = helpers.run_tiny(root, "tiny_slab4.fwd")
            finally:
                mp.undo()
                jax.clear_caches()
            plan = [ln for ln in text.splitlines() if "plan:" in ln]
            report[name] = {"rc": rc, "plan": plan, "result": res}
    Path(out_path).write_text(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
