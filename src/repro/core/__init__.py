"""The paper's contribution: planned, variant-swappable, distributed FFT."""

from . import algo, api, comm, dfft, fftconv, plan, variants, wisdom
from .algo import fft, fft2, ifft, irfft, rfft, rfft2, to_complex, to_pair
from .api import (NdPlan, execute_nd, execute_nd_inverse, fftn, ifftn,
                  irfftn, plan_nd, rfftn)
from .comm import (COMM_BACKENDS, AgasBackend, CollectiveBackend, CommBackend,
                   PipelinedBackend, get_backend, measure_comm,
                   measure_comm_conv, measure_comm_factor1d,
                   measure_comm_gather, measure_comm_pencil,
                   measure_comm_pencil_nd, measure_comm_slab,
                   measure_comm_slab_nd, pad_to, plan_comm, plan_comm_conv,
                   plan_comm_factor1d, plan_comm_gather, plan_comm_pencil,
                   plan_comm_pencil_nd, plan_comm_slab_nd,
                   resolve_axis_backends)
from .dfft import (collect, distribute, fft2_slab, fft3_pencil, ifft2_slab,
                   ifft3_pencil, irfft3_pencil, rfft3_pencil)
from .fftconv import factor_split, fft_conv, fft_conv_seq_sharded
from .plan import (CPU_LOCAL, TPU_V5E, Plan, Planner, execute,
                   execute_inverse, hardware_for)
from .variants import VARIANTS, run_variant
from .wisdom import WisdomStore

__all__ = [
    "algo", "api", "comm", "dfft", "fftconv", "plan", "variants",
    "wisdom",
    "fft", "ifft", "rfft", "irfft", "fft2", "rfft2",
    "to_pair", "to_complex",
    # the planned N-D front-end (use these; the *_slab/*_pencil entry
    # points below are deprecated shims)
    "NdPlan", "plan_nd", "execute_nd", "execute_nd_inverse",
    "fftn", "ifftn", "rfftn", "irfftn",
    "COMM_BACKENDS", "CommBackend", "CollectiveBackend", "PipelinedBackend",
    "AgasBackend", "get_backend", "resolve_axis_backends", "pad_to",
    "plan_comm", "plan_comm_slab_nd", "plan_comm_pencil",
    "plan_comm_pencil_nd", "plan_comm_conv", "plan_comm_factor1d",
    "plan_comm_gather",
    "measure_comm", "measure_comm_slab", "measure_comm_slab_nd",
    "measure_comm_pencil", "measure_comm_pencil_nd", "measure_comm_conv",
    "measure_comm_factor1d", "measure_comm_gather",
    "WisdomStore",
    "fft2_slab", "ifft2_slab",
    "fft3_pencil", "ifft3_pencil", "rfft3_pencil", "irfft3_pencil",
    "distribute", "collect",
    "factor_split", "fft_conv", "fft_conv_seq_sharded",
    "Plan", "Planner", "execute", "execute_inverse", "TPU_V5E", "CPU_LOCAL",
    "hardware_for",
    "VARIANTS", "run_variant",
]
