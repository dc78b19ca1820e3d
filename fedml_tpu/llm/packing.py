"""Document packing for ``LLMTrainer``: concat-and-chunk, with the document
of every token beside it.

``pack(documents, seq_len)`` lays the documents end to end, in order, and cuts
the stream into rows of ``seq_len`` tokens (TRL's ``packing=True``): no token
is dropped and only the last row is padded.  A document that crosses a row's
end continues as the next row's first document.  Beside ``tokens`` and
``targets`` (the stream's next token) a row carries ``segments``: one id per
document of the row, from 1 up, and 0 for padding.  A model given them
(``models/transformer.py``) lets neither state, convolution nor attention
reach across a document's start, and the loss counts the positions whose
target lies in the same document (``targets_in_document``): every token but a
document's last, the row's last and padding.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np


def pack(documents: Iterable[Sequence[int]], seq_len: int):
    """documents: token-id sequences -> ``(tokens, targets, segments)``, each
    (rows, seq_len) int32."""
    docs = [np.asarray(d, np.int32) for d in documents if len(d)]
    if not docs:
        raise ValueError("nothing to pack")
    stream = np.concatenate(docs)
    owner = np.repeat(np.arange(len(docs), dtype=np.int64), [len(d) for d in docs])
    rows = -(-len(stream) // seq_len)
    pad = rows * seq_len - len(stream)
    tokens = np.pad(stream, (0, pad)).reshape(rows, seq_len)
    targets = np.pad(stream[1:], (0, pad + 1)).reshape(rows, seq_len)
    owner = np.pad(owner, (0, pad), constant_values=-1).reshape(rows, seq_len)
    # ids count a row's documents from 1: a document's index less that of the row's first
    segments = np.where(owner < 0, 0, owner - owner[:, :1] + 1).astype(np.int32)
    return tokens, targets, segments
