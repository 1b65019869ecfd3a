"""What the five `setup_*` readers share (benchmarks/layer_metrics, PR 58):
the children of one labelled series out of a counters snapshot. They read
`Window.counters_start` alone: the snapshot is taken where the window
starts, which is where the set-up ends, and the program's start-up series
are cumulative since the process began (docs/OBSERVABILITY.md "Start-up
timeline")."""

import re

_LABEL = re.compile(r'([a-z_]+)="([^"]*)"')


def children(snap, name):
    """{(label values in label-name order): value} of the series `name` in
    a snapshot of stack.parse_metrics (which keeps a labelled series under
    its full name, labels sorted by name); None where the program has no
    such series."""
    head = name + "{"
    found = {
        tuple(v for _, v in sorted(_LABEL.findall(k[len(head):]))): val
        for k, val in snap.items() if k.startswith(head)
    }
    return found or None
