"""Packed rows: what the ops that take ``segments`` (document ids, equal along
a document) share, so that neither the scan nor the attention imports the
other."""

from __future__ import annotations

import jax.numpy as jnp


def document_index(segments):
    """segments: (b, s) ids, equal along a document -> (b, s) int32 that
    counts the documents of a row from 0: rises by one wherever the id
    changes, so two tokens share a document iff they share an index, whatever
    ids the feed reuses."""
    starts = segments[:, 1:] != segments[:, :-1]
    return jnp.pad(jnp.cumsum(starts, axis=1, dtype=jnp.int32), ((0, 0), (1, 0)))
