"""A reduced-width cell for CPU tests: the moonlight-5l file with small
widths (16 experts top-6 on 4 virtual ranks, so a vision-heavy batch
opens the LB gate and fires FP4) and a small closed-loop vision mix."""
import json
import time

import _paths
from harness import spec

CELL = "tiny.vision-closed"


def config() -> dict:
    conf = json.loads((_paths.BENCH / "configs" / "moonlight-5l.json")
                      .read_text())
    conf.update(hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                n_routed_experts=16, num_experts_per_tok=6,
                n_shared_experts=1, vocab_size=4096)
    conf["assumed"] = dict(conf["assumed"], head_dim=16)
    return conf


def mix(**over) -> dict:
    m = {"kind": "closed", "clients": 8, "think_s": 0.0,
         "prompt_len": {"min": 44, "max": 60},
         "vision_frac": {"mean": 0.72, "std": 0.15, "min": 0.0, "max": 0.95},
         "output_len": {"min": 4, "max": 8},
         "engine": {"max_slots": 8, "max_len": 72, "prefill_budget": 4096,
                    "virtual_ep": 4},
         "trace_tail_s": 1.0, "check_sample": 4}
    m.update(over)
    return m


def cell(root, limit: float, **over) -> spec.Cell:
    """The tiny cell, checked against ``limit``."""
    return spec.Cell(name=CELL, config_name="tiny", traffic_name="tiny",
                     chips=1, config=config(), traffic=mix(**over),
                     settings={"mean_logit_gap": limit},
                     end_to_end=[], per_layer=[])


class StepClock:
    """A window clock that moves one second per engine step, so a window
    of ``n`` seconds runs ``n`` steps however busy the CPU is."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def hook(self, then=None):
        """An engine hook that counts the steps, after ``then``."""
        def hook(eng):
            if then is not None:
                then(eng)
            step = eng.step

            def counted():
                out = step()
                self.t += 1.0
                return out
            eng.step = counted
        return hook


def run(root, limit, seed=2 ** 33 + 5, seconds=2.0, mix_over=None,
        steps=None, engine_hook=None, **kw) -> dict:
    """One run of the tiny cell: ``seconds`` of wall clock, or ``steps``
    engine steps."""
    import jax
    import run as bench_run
    if steps is not None:
        clock = StepClock()
        kw.update(clock=clock)
        engine_hook, seconds = clock.hook(engine_hook), float(steps)
    return bench_run.run_cell(cell(root, limit, **(mix_over or {})), seed,
                              seconds, False,
                              time.perf_counter(), jax.devices(),
                              root=_paths.ROOT, engine_hook=engine_hook,
                              **kw)
