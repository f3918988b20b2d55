"""Four-chip cells rehearsed on four virtual CPU devices, in a process of
its own (the device count is fixed when JAX starts).

    python bench/tests/_ep4_worker.py

Prints one JSON line: whether the sharded weight draw equals the
one-device draw bit for bit and how the expert stacks are spread, then
runs of the tiny cell at EP=4 (mesh ``(1, 4)``, dropless capacity): the
program with its fp8 control, and the timed path broken underneath (the
all-to-all exchange left out; a served token altered where it is
sampled; a decode step that returns its cache unchanged), each with
``correct`` and the numbers compared.
"""
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import _paths  # noqa: E402,F401
import _tiny  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import arch, model, spec, weights  # noqa: E402

LIMIT = 1e-3
SEED = 2 ** 33 + 41


def config() -> dict:
    conf = _tiny.config()
    conf["num_hidden_layers"] = 4
    conf["assumed"] = dict(conf["assumed"], capacity_factor=4.0)
    return conf


def cell() -> spec.Cell:
    mix = _tiny.mix(engine={"max_slots": 8, "max_len": 72,
                            "prefill_budget": 4096, "virtual_ep": None},
                    check_sample=12)
    return spec.Cell(name="tiny-ep4.vision-closed", config_name="tiny-ep4",
                     traffic_name="tiny", chips=4, config=config(),
                     traffic=mix, settings={"mean_logit_gap": LIMIT},
                     end_to_end=[], per_layer=[])


def draws() -> dict:
    mod, a = arch.of(config())
    cfg = mod.model_config("tiny-ep4", a)
    one = jax.device_get(weights.draw(mod, a, SEED))
    from repro.models.common import use_mesh
    with use_mesh(model.mesh_of(4)):
        sharded = weights.draw(mod, a, SEED, model.shardings(cfg))
    same = jax.tree.map(lambda x, y: bool(np.array_equal(
        np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8))),
        one, jax.device_get(sharded))
    w = sharded["blocks"]["layer0"]["moe"]["w_gate"]
    return {"draw_equal": all(jax.tree.leaves(same)),
            "leaves": len(jax.tree.leaves(same)),
            "w_gate_shards": sorted((s.device.id, s.data.shape[1])
                                    for s in w.addressable_shards)}


def no_exchange(eng):
    """The EP layer's all-to-alls left out: each rank keeps what it would
    have sent."""
    from repro.core import ep_moe
    real = ep_moe._dist_comm

    def comm(ep, fsdp):
        return real(ep, fsdp)._replace(a2a=lambda x: x)
    ep_moe._dist_comm = comm


def summary(res) -> dict:
    return {"correct": res["correct"],
            "control_correct": res.get("control_correct"),
            "attempted": res["attempted"], "failed": res["failed"],
            **{k: v["value"] for k, v in res["checked"].items()}}


def serve(steps=60, **kw) -> dict:
    clock = _tiny.StepClock()
    hook = kw.pop("engine_hook", None)
    import run
    return summary(run.run_cell(cell(), SEED, float(steps), False,
                                time.perf_counter(), jax.devices(),
                                engine_hook=clock.hook(hook), clock=clock,
                                **kw))


def main() -> int:
    out = draws()
    out["program"] = serve(control=True)
    import test_bench_correctness as tc
    out["token_altered"] = serve(engine_hook=tc._alter_tokens)
    # a kept cache flips few tokens: more steps, more of them checked
    out["decode_state_unchanged"] = serve(
        steps=150, engine_hook=tc._decode_keeps_state)
    out["no_exchange"] = serve(engine_hook=no_exchange)   # last: it stays
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
