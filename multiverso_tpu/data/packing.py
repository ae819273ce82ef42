"""Documents packed into fixed training sequences.

A language-model step wants ``[sequences, length]`` token slots; a
corpus gives documents of any length. :func:`pack_documents` lays whole
documents into the sequences of a step, first fit in arrival order, and
says of every slot which document it belongs to and where in it:

- ``tokens`` int32 ``[B, S]``: the ids, 0 on padding;
- ``doc`` int32 ``[B, S]``: 1, 2, … per document of the sequence, 0 on
  the padded tail (so ``doc > 0`` is "a real token", and two slots
  belong to one document iff their ids are equal and non-zero);
- ``pos`` int32 ``[B, S]``: the position inside the document, from 0.

``open_sequences`` sequences are open at a time (four steps' worth
unless given): a document goes into the first of them that has room, and
when it fits none the fullest one is closed and a fresh one takes its
place. Closed sequences leave in the order they closed, ``B`` to a step.
A document longer than ``S`` is cut to its first ``S`` tokens (the tail
would be a context nobody trained on).
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional

import numpy as np

Batch = Dict[str, np.ndarray]


def pack_documents(docs: Iterable[np.ndarray], sequences: int,
                   length: int,
                   open_sequences: Optional[int] = None) -> Iterator[Batch]:
    """Yield whole steps for as long as ``docs`` fills them; sequences
    still open when ``docs`` ends are dropped (a step is a fixed shape)."""
    pool = open_sequences or 4 * sequences
    rows = {k: np.zeros((pool, length), np.int32)
            for k in ("tokens", "doc", "pos")}
    used, count = np.zeros(pool, np.int64), np.zeros(pool, np.int32)
    closed: List[Batch] = []
    for d in docs:
        d = np.asarray(d, np.int32)[:length]
        n = len(d)
        if n == 0:
            continue
        fits = np.flatnonzero(used + n <= length)
        if len(fits) == 0:
            b = int(np.argmax(used))
            closed.append({k: v[b].copy() for k, v in rows.items()})
            for v in rows.values():
                v[b] = 0
            used[b] = count[b] = 0
            if len(closed) == sequences:
                yield {k: np.stack([c[k] for c in closed]) for k in rows}
                closed = []
        else:
            b = int(fits[0])
        a = int(used[b])
        count[b] += 1
        rows["tokens"][b, a:a + n] = d
        rows["doc"][b, a:a + n] = count[b]
        rows["pos"][b, a:a + n] = np.arange(n, dtype=np.int32)
        used[b] += n


def real_tokens(batch: Batch) -> int:
    return int(np.count_nonzero(batch["doc"]))
