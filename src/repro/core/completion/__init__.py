from repro.core.completion.als import (als_sweep, als_sweep_explicit,
                                       als_sweep_stats, batched_cg,
                                       batched_pcg)
from repro.core.completion.ccd import ccd_sweep, ccd_sweep_tttp
from repro.core.completion.gauss_newton import GGNState, ggn_init, ggn_sweep
from repro.core.completion.sgd import sgd_sweep
from repro.core.completion.gcp import gcp_step, gcp_adam_init

__all__ = ["als_sweep", "als_sweep_explicit", "als_sweep_stats",
           "batched_cg", "batched_pcg",
           "ccd_sweep", "ccd_sweep_tttp", "sgd_sweep", "gcp_step",
           "gcp_adam_init", "GGNState", "ggn_init", "ggn_sweep"]
