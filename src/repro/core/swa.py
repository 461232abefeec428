"""Sequential SWA baseline (Izmailov et al. 2018) for the Table-4
comparison: cyclic learning rate, one model sampled at each cycle boundary,
streaming average (swa_avg kernel path on TPU), BN recompute at the end.
"""
from __future__ import annotations

from typing import Dict

import jax

from repro import obs
from repro.configs.base import SWAConfig
from repro.core.averaging import StreamingAverage
from repro.core.schedules import schedule_fn as make_schedule
from repro.data.pipeline import Loader
from repro.train.precision import default_scale_state


class SWA:
    def __init__(self, adapter, cfg: SWAConfig, train_arrays: Dict,
                 test_loader: Loader):
        self.adapter = adapter
        self.cfg = cfg
        self.train_arrays = train_arrays
        self.test_loader = test_loader

    def run(self, bundle, opt_state=None) -> Dict:
        """Starts from ``bundle`` (fresh init, a large-batch model, or the
        small-batch optimum — the three rows of Table 4)."""
        cfg = self.cfg
        adapter = self.adapter
        loader = Loader(self.train_arrays, cfg.batch_size, seed=cfg.seed)
        sched = make_schedule(cfg.schedule)
        step_fn = jax.jit(adapter.make_train_step(sched),
                          donate_argnums=(0, 1))
        opt_state = opt_state if opt_state is not None \
            else adapter.init_opt(bundle)
        scale = default_scale_state()   # SWA baseline trains plain f32

        with obs.span("SWA.run") as span:
            avg = StreamingAverage()
            total_steps = cfg.n_samples * cfg.cycle_steps
            for step in range(total_steps):
                batch = loader.batch(step)
                bundle, opt_state, scale, metrics = step_fn(
                    bundle, opt_state, batch, step, scale)
                if (step + 1) % cfg.cycle_steps == 0:
                    avg.add(bundle["params"])
            last_acc = adapter.eval_accuracy(bundle, self.test_loader)
            with obs.span("SWA.bn_recompute"):
                final = adapter.finalize(avg.value(), loader)
        return {
            "before_avg_test_acc": last_acc,
            "after_avg_test_acc": adapter.eval_accuracy(final,
                                                        self.test_loader),
            "time": span.seconds,
            "n_samples": avg.n,
            "final_bundle": final,
            "last_bundle": bundle,
        }
