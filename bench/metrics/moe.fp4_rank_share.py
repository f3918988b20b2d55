"""Mean over the window's prefill iterations of the share of EP ranks the
ReaLB policy ran in FP4, in per cent: the engine's ``IterStats.fp4_ranks``
(flagged ranks, averaged over MoE layers) over the policy's EP group
(virtual ranks on one chip, one rank a chip on several)."""


def read(run):
    pre = [s for s in run.iter_stats if s.phase == "prefill"]
    if not pre:
        return None
    return 100.0 * sum(s.fp4_ranks for s in pre) / len(pre) / run.policy_ranks
