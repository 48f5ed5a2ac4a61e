"""Median, over the decode iterations of the traced part, of the width
in blocks of the block table the loop handed the step: ``blocks`` on
``dml.serve.step.dispatch``. The dense step reads every position its
table spans, so this is what the step's device time scales with. A
program that hands its step one width (PR 31's parent, whose dispatch
span has no such fact, and PR 23's, which opens no span) reads that
width: the full table, ``ceil((max_prompt_len + max_new_tokens) /
block_size)``, of the journal's ``decode_start`` record or, where the
journal was cut to its requests, of the cell's configuration, which the
replica was built from. An instrumented program without the dispatch
span reads nothing. Layer: decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import cell as cell_lib, program_trace
from benchmark.lib.stats import percentile


def full_table_blocks(run: dict) -> float:
    """The one width of a program that does not choose: as its journal
    says it, else as the configuration of the run's cell (the work
    directory's name) does, under the same three names."""
    records, _, _ = program_trace.journal(run)
    said = next((r for r in records if r.get("action") == "decode_start"),
                None)
    if said is None:
        said = cell_lib.load_cell(
            run["workdir"].name).config["serve"]["decode"]
    return float(-(-(said["max_prompt_len"] + said["max_new_tokens"])
                   // said["block_size"]))


def read(trace: dict, counters: dict) -> float | None:
    run = program_trace.this_run(trace)
    dispatches = [e[4] for events in program_trace.spans_by_thread(
                      run["trace"]).values()
                  for e in events if e[0] == program_trace.SPAN_DISPATCH]
    blocks = [facts["blocks"] for facts in dispatches if "blocks" in facts]
    if blocks:
        return float(percentile(blocks, 0.5))
    if not dispatches and program_trace.instrumented(run["trace"]):
        return None        # spans, and not this one: not a width
    return full_table_blocks(run)
