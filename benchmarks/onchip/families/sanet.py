"""SA-Net (FedKBP+, arXiv:2505.14507, Fig. 5) as a model family of the
benchmark: the program's volumetric ``dose`` and ``seg`` tasks, the case
pool of ``fedbench.pool``, the plain reference of ``fedbench.reference``
and the convolution counts of ``fedbench.counts``.

A configuration file that says ``"family": "sanet"`` gives ``task``
(``dose`` or ``segmentation``), ``volume``, ``in_channels``,
``out_channels``, ``base_filters``, ``num_levels`` and ``se_ratio``.
"""
from __future__ import annotations

from typing import Any, Dict

from fedbench import counts, reference
from fedbench.pool import CasePool


def task_fields(cfg: dict, traffic: dict, job_seed: int) -> Dict[str, Any]:
    """``TaskConfig`` fields for the configuration and traffic."""
    f = dict(sites=traffic["sites"], batch=traffic["batch"],
             volume=tuple(cfg["volume"]), base_filters=cfg["base_filters"],
             num_levels=cfg["num_levels"], seed=job_seed)
    if cfg["task"] == "dose":
        f.update(kind="dose", num_oars=cfg["in_channels"] - 2)
    else:
        f.update(kind="seg", in_channels=cfg["in_channels"],
                 num_classes=cfg["out_channels"])
    return f


def check_model(cfg: dict, model_cfg) -> None:
    """The program builds the configuration as the file states it."""
    want = {"in_channels": cfg["in_channels"],
            "out_channels": cfg["out_channels"],
            "base_filters": cfg["base_filters"],
            "num_levels": cfg["num_levels"], "se_ratio": cfg["se_ratio"],
            "deep_supervision": True}
    got = {k: getattr(model_cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program model {got} is not the configuration "
                         f"{want}")


# ``make_pool(cfg, site_cases, pool_key, draw)``, ``run_reference(cfg,
# opt, site_cases, batch_fn, init_key, rounds, local_steps, precision,
# device)``, ``round_flops(cfg, traffic)`` and ``num_params(cfg)``
make_pool = CasePool
run_reference = reference.run_rounds
round_flops = counts.round_flops
num_params = counts.num_params
