"""`source_detail.of` (`learn_mfu`: the learn step's operations from
shapes over ALL busy seconds and the bf16 peak) taken over the seconds
the device spent under `source_detail.scopes` instead: the same count of
operations through the same reducer, so the two differ only in the time.
Under 100 % unless operations are counted too high or the scopes leave
out part of the step (the contract refuses above 105 %)."""

import discover
import scope_read


def reduce(facts: dict, spec: dict):
    src = spec["source_detail"]
    of = discover.data(facts["data_dir"], "layer_metrics", src["of"])
    whole = discover.module(facts["data_dir"], "reducers",
                            of["reducer"]).reduce(facts, of)
    seconds = scope_read.scope_seconds(facts, src["scopes"], src.get("less", ()))
    if whole is None or not seconds:
        return None
    return whole * facts["trace"]["busy_s"] / seconds
