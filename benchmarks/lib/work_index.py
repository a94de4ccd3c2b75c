"""The least bytes an index-rung gather asks of the chip, from the query.

The rung resolves a query's docIds on the host and launches one program
(``jit_index_gather_agg``) over them, padded to a power-of-two
``capacity`` (the ``Kernel`` span of the launch carries it). The least the
device can move for that: the docIds themselves, and for every column the
query groups by or sums one staged forward-index entry a docId. The filter's
columns are not read on the device (the host resolved them), and a numeric
column's dictionary lookup is left out: a floor. Reads only the query as
data (``value`` / ``group_by`` of the traffic file), never the engine.
"""

from __future__ import annotations

from typing import Any, Dict, List

DOCID_BYTES = 4     # the padded int32 docId array
FWD_BYTES = 4       # a staged forward-index entry (dictIds ride as int32)


def gathered_columns(query: Dict[str, Any]) -> List[str]:
    """The columns the gather reads: what the query sums and groups by."""
    named = [v for v in query["value"] if v not in ("*", "-")]
    return sorted(set(named + list(query["group_by"])))


def gather_least_bytes(query: Dict[str, Any], capacity: int) -> float:
    return float(capacity) * (DOCID_BYTES
                              + FWD_BYTES * len(gathered_columns(query)))
