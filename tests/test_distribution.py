"""Multi-device correctness, each check in a subprocess with 8 fake CPU
devices (jax locks the device count at first init, so the main pytest
process must stay single-device for the smoke tests)."""
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow    # each check compiles a mesh subprocess

WORKER = pathlib.Path(__file__).parent / "_dist_worker.py"

CHECKS = [
    "ep_dispatch_matches_local",
    "ep_broadcast_matches_local",
    "realb_fp4_rank_activates",
    "chunk_padding_isolated_under_ep",
    "placement_identity_bitwise_under_ep",
    "placement_permuted_matches_local_under_ep",
    "virtual_ep_policy_parity",
    "replication_identity_bitwise_under_ep",
    "replication_split_under_ep",
    "perlayer_identity_bitwise_under_ep",
    "perlayer_tables_matches_local_under_ep",
    "async_migrate_chunks_match_sync_under_ep",
    "replica_capacity_reduced_cap",
    "model_train_step_under_mesh",
    "decode_under_mesh",
    "elastic_reshard",
    "weighted_split_under_ep",
    "elastic_kill_rejoin_under_ep",
    "kernel_fp4_parity_under_ep",
    "collective_census_reconciles",
    "fp4_transform_placement_under_ep",
]


@pytest.mark.parametrize("check", CHECKS)
def test_distributed(check):
    r = subprocess.run([sys.executable, str(WORKER), check],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{check}\n--- stdout ---\n{r.stdout}" \
                              f"\n--- stderr ---\n{r.stderr[-3000:]}"
