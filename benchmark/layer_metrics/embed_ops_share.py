"""Share of the fullest device's busy time spent on the embedding path of
a Wide & Deep step: the lookups (embedding rows and wide weights), their
gradient's accumulation into the tables and the tables' update.

The program has no kernel of its own there: the path is XLA's, compiled
from `models/wdl.forward` (scopes `embed`, `wide`), its backward pass and
the optimizer (`table_update`). A profiler event carries the instruction's
text and not its scope, so the path's events are found by what only they
hold among the instruction's operands and results, all from the
configuration: an array with the tables' row count (the sum of
`vocab_sizes`, or that count cut by the 128 // `embed_size` rows the
program packs into a 128-lane row), an array with a batch's lookup count
(`batch_rows` x columns: the gathered rows, the sorted ids of the
scatter-add), or a batch's looked-up block (batch_rows, columns, embed_size
or 128). Self times are summed, so a `while` that carries the tables
counts for nothing but itself. `table_events` is shared with
`embed_roofline`.

As compiled at PR 26 (names change with the compiler; the shapes do not):
`fusion.319` the gather of packed rows, `compare_select_fusion.15` and
`slice_add_fusion.8` the select of a row's quarter, `compare_select_fusion.16`
its transpose, `sort.24`/`sort.25` and `fusion.337` the scatter-add into
zeros, `fusion.338` AdaGrad over the embedding table, `fusion.323`,
`fusion.340`, `fusion.341` the wide table's gather, scatter-add and
AdaGrad, and the `reshape`/`copy` between them.
"""

import re

LANES = 128


def table_events(context):
    """The device events of the embedding path, or [] where the
    configuration has no tables (another family's cell)."""
    config = context["config"]
    if "vocab_sizes" not in config:
        return []
    rows, width = sum(config["vocab_sizes"]), config["embed_size"]
    pack = LANES // width if LANES % width == 0 else 1
    batch, cols = config["batch_rows"], len(config["vocab_sizes"])
    counts = "|".join(str(n) for n in {rows, -(-rows // pack), batch * cols})
    holds = re.compile(r"[\[,](?:%s)[,\]]|\[(?:1,)?%d,%d,(?:%d|%d)\]"
                       % (counts, batch, cols, width, LANES))
    dev = context["trace"].device(context["fullest_device"])
    return [e for e in dev.ops if holds.search(e.detail)]


def read(context):
    dev = context["trace"].device(context["fullest_device"])
    events = table_events(context)
    if not events or not dev.busy_s:
        return None
    return 100.0 * sum(e.self_s for e in events) / dev.busy_s
