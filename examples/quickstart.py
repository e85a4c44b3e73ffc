"""Quickstart: the paper's 2D FFT through the public API.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import Planner, fft_conv, run_variant, VARIANTS
from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 512)).astype(np.float32)
    ref = np.fft.rfft2(x)

    # 1) FFTW-style planning: estimate (cost model) picks the factorization
    planner = Planner(mode="estimate", backends=("jnp",))
    plan = planner.plan(512, kind="r2c")
    print(f"plan for n=512 r2c: factors={plan.factors} backend={plan.backend}")

    # 2) the paper's implementation variants all agree with numpy
    for name in VARIANTS:
        out = run_variant(name, x, planner)
        z = np.asarray(out[0]) + 1j * np.asarray(out[1])
        err = np.max(np.abs(z - ref)) / np.max(np.abs(ref))
        print(f"variant {name:13s} rel_err={err:.2e}")

    # 3) FFT convolution (the technique as an LM sequence mixer)
    u = rng.standard_normal((2, 256, 8)).astype(np.float32)
    k = (rng.standard_normal((8, 256))
         * np.exp(-np.arange(256) / 16.0)).astype(np.float32)
    y = fft_conv(u, k, planner)
    print(f"fft_conv output {y.shape}, finite={bool(np.isfinite(np.asarray(y)).all())}")


if __name__ == "__main__":
    main()
