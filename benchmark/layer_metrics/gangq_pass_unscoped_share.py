"""gangq_pass_unscoped_share: the share of the pass program's device time
(``jit_per_scenario_retry``, in a batch under ``retry_groups``) that its
stage table files under NO scope, in %: copies and moves the compiler makes
of what the program carries (the record's arrays among them) that no named
stage of the program answers for. Lower is better: what it holds is cost
that no other ``gangq_*`` metric shows."""

from layer_metrics import _gangq, _program_stages


def read(ctx):
    if not _gangq.under_groups(ctx):
        return None
    got = _program_stages.read(ctx, _gangq.PASS)
    total = got["op_seconds"]
    return 100.0 * got["seconds"].get("", 0.0) / total if total else None
