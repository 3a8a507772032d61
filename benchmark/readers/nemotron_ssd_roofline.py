"""Share of its roofline the state space's sequential pass reached in the
Nemotron-H stack, in %: ``readers/ssd_roofline.site_least`` (one forward and
one backward of every held head over every trained row, all float32) with
THIS configuration's keys (8 heads of 64 channels in one group, a state of
128, chunks of 128) and its ``M`` layers alone -- the pattern's, not
``num_hidden_layers`` -- over ALL device self time under ``args["scopes"]``
(the program's ``ssm.chunk``).  A configuration of another block has no
pattern: nothing to read."""

from benchmark.readers import roofline, ssd_roofline


def required(evidence: dict) -> list:
    cfg = evidence["config"]
    if "hybrid_override_pattern" not in cfg:
        return []
    layers = cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]].count("M")
    tokens = cfg["engine_json"]["preparator"]["params"]["rowLen"]
    return [
        ssd_roofline.site_least(
            kind, roofline.trained_rows(cfg), cfg["mamba_num_heads"], cfg["n_groups"],
            tokens, cfg["chunk_size"], cfg["mamba_head_dim"], cfg["ssm_state_size"])
        for kind in ("fwd", "bwd")
    ] * layers


def read(evidence: dict, args: dict):
    return roofline.share_pct(evidence, args, required)
