"""How often a block's commit rode in the call that opens the next block, from
the program's trace session (``--trace 2``): ``blockdiff_fused_commit_share.batch``
= the engine's counter ``block_commits_fused`` over ``block_commit_passes``, of
the commits (units of B rows that ran a block's final ids through the stack to
leave its K and V) read in the traced seconds the share that went through as
commit rows of the call that ran the first denoising pass of the block after
them (``vescale_tpu/models/sdar_moe.py:serve_decode``); the rest were calls of
their own: a request's last block, or a caller that did not ask to fuse.  A
program without the counter (before PR 39), or a session that read no commit,
leaves the metric out."""

from benchmark.layer_metrics import _session as s

METRICS = {"blockdiff_fused_commit_share.batch": {"unit": "%", "layer": "Block diffusion", "moves": "serve_tokens_per_s"}}


def read(run):
    session = s.reduced(run)
    if s.suffix(run) != "batch" or session is None:
        return {}
    counters = session["counters"]
    commits = counters.get("block_commit_passes") or 0
    if not commits or "block_commits_fused" not in counters:
        return {}
    return {"blockdiff_fused_commit_share.batch": 100.0 * counters["block_commits_fused"] / commits}
