"""Trainer: the fault-tolerant training loop.

* checkpoint/restart via CheckpointManager (atomic, keep-k, elastic);
* preemption-safe: SIGTERM/SIGINT triggers a final checkpoint before exit
  (the TPU-pod eviction contract);
* straggler/data-fault mitigation: a batch source that raises is skipped
  and logged (``max_data_retries``), keeping the step counter deterministic;
* JSONL metrics stream (one line per step — the thing dashboards tail);
* mesh-aware: when given a mesh + sharding rules it jits the train step
  with explicit in/out shardings and enters the activation-sharding scope.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

from ..dist.sharding import activation_sharding, bind_shardings, spec_tree
from ..optim.adamw import AdamWConfig, init_adamw, make_train_step
from .checkpoint import CheckpointManager


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep_ckpts: int = 3
    metrics_path: Optional[str] = None
    max_data_retries: int = 3


class Trainer:
    def __init__(
        self,
        loss_fn: Callable,                  # (params, batch) -> (loss, metrics)
        params: Any,
        opt_cfg: AdamWConfig,
        cfg: TrainerConfig,
        *,
        mesh=None,
        param_rules=None,
        accum_steps: int = 1,
        grad_transform=None,
    ):
        self.cfg = cfg
        if mesh is not None:
            # the step relies on GSPMD propagation from its in/out shardings
            # (Auto axes); jax.make_mesh now defaults to Explicit axes
            mesh = jax.sharding.Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
        self.mesh = mesh
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep_ckpts)
        self.params = params
        self.opt_state = init_adamw(params, opt_cfg)
        self.step = 0
        self._stop = False
        self._metrics_f = None

        step_fn = make_train_step(loss_fn, opt_cfg, accum_steps=accum_steps,
                                  grad_transform=grad_transform)
        if mesh is not None and param_rules is not None:
            specs = spec_tree(params, param_rules, mesh)
            self.param_shardings = bind_shardings(mesh, specs)
            opt_specs = {"m": specs, "v": specs, "step": ()}
            self.opt_shardings = bind_shardings(mesh, opt_specs)
            self.params = jax.device_put(self.params, self.param_shardings)
            self.opt_state = jax.device_put(self.opt_state, self.opt_shardings)
            self._step_fn = jax.jit(
                step_fn,
                in_shardings=(self.param_shardings, self.opt_shardings, None),
                out_shardings=(self.param_shardings, self.opt_shardings, None),
                donate_argnums=(0, 1),
            )
        else:
            self.param_shardings = None
            self.opt_shardings = None
            self._step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    # -- preemption ------------------------------------------------------
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._stop = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not main thread

    # -- checkpointing -----------------------------------------------------
    def save(self):
        state = {"params": self.params, "opt": self.opt_state}
        path = self.ckpt.save(self.step, state, extra={"step": self.step})
        return path

    def maybe_restore(self) -> bool:
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        template = {"params": self.params, "opt": self.opt_state}
        shardings = None
        if self.param_shardings is not None:
            shardings = {"params": self.param_shardings, "opt": self.opt_shardings}
        state, step = self.ckpt.restore(template, shardings=shardings)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.step = step
        return True

    # -- metrics -----------------------------------------------------------
    def _log(self, metrics: dict):
        if self.cfg.metrics_path:
            if self._metrics_f is None:
                os.makedirs(os.path.dirname(self.cfg.metrics_path) or ".", exist_ok=True)
                self._metrics_f = open(self.cfg.metrics_path, "a")
            rec = {"step": self.step,
                   **{k: float(np.asarray(v)) for k, v in metrics.items()}}
            self._metrics_f.write(json.dumps(rec) + "\n")
            self._metrics_f.flush()

    # -- the loop ------------------------------------------------------------
    def fit(self, batches: Iterator, verbose: bool = False) -> dict:
        self._install_signal_handlers()
        scope = activation_sharding(self.mesh) if self.mesh is not None else _null()
        history = []
        with scope:
            while self.step < self.cfg.total_steps and not self._stop:
                batch = None
                for attempt in range(self.cfg.max_data_retries):
                    try:
                        batch = next(batches)
                        break
                    except StopIteration:
                        self._stop = True
                        break
                    except Exception as e:  # data fault: skip and log
                        self._log({"data_fault": 1.0})
                        if verbose:
                            print(f"[trainer] data fault (attempt {attempt}): {e}")
                if batch is None or self._stop:
                    break
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch)
                self.step += 1
                if self.step % self.cfg.log_every == 0 or self.step == 1:
                    metrics = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    history.append({"step": self.step, **metrics})
                    self._log(metrics)
                    if verbose:
                        print(f"[trainer] step {self.step}: " +
                              " ".join(f"{k}={v:.4g}" for k, v in metrics.items()))
                if self.step % self.cfg.ckpt_every == 0:
                    self.save()
        self.save()  # preemption / completion checkpoint
        if self._metrics_f:
            self._metrics_f.close()
            self._metrics_f = None
        return {"final_step": self.step, "history": history}


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
