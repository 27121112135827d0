"""Seconds of the timed process's start, `[process start, t0]`, that fall
in `source_detail.bucket`, from the program's own record in its log
(`start_read.start_buckets`: every instant in one bucket, first rule
that applies; `gc` beside them). A program without the record reads 0.0
in every named bucket and the whole interval in `other`, with a note."""

import start_read


def reduce(facts: dict, spec: dict):
    return start_read.start_buckets(facts)[spec["source_detail"]["bucket"]]
