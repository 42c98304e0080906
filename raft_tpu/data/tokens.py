"""Packed token sequences with document boundaries, for the token family
(``lfm2_moe``).

A batch is ``tokens``, ``segment_ids``, ``positions``, each
``(batch, seq_len)`` int32: documents laid end to end with no padding,
the last of a sequence truncated at its end; ``segment_ids`` numbers
the documents of a sequence from 0, ``positions`` restart at each.

Two sources behind one loader:

* a token file: ``<path>.npz`` with ``tokens`` (1-D integers) and
  ``offsets`` (document starts, ascending, first 0). Sequence ``i`` is
  tokens ``[i * seq_len, (i + 1) * seq_len)`` of the stream; a document
  that straddles the cut continues in the next sequence as a new one.
* none given: documents whose lengths are log-normal (median 700,
  sigma 1.2, cut at ``seq_len``) and whose ids are uniform over the
  vocabulary, drawn from ``(seed, sequence index)`` alone, so any
  sequence can be made again.

The cursor is the number of sequences yielded to the consumer; it rides
the checkpoint like the flow loaders' (``state()`` / ``load_state()``:
exact-cursor resume).
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional

import numpy as np

from raft_tpu.data.datasets import LoaderState


def pack_documents(lengths, seq_len: int):
    """``segment_ids`` and ``positions`` of one sequence packed from
    documents of ``lengths`` (the last truncated at ``seq_len``; they
    must reach it)."""
    segment_ids = np.empty(seq_len, np.int32)
    positions = np.empty(seq_len, np.int32)
    at = 0
    for doc, length in enumerate(lengths):
        length = min(int(length), seq_len - at)
        segment_ids[at:at + length] = doc
        positions[at:at + length] = np.arange(length)
        at += length
        if at == seq_len:
            return segment_ids, positions
    raise ValueError(f"documents of {sum(lengths)} tokens do not fill a "
                     f"sequence of {seq_len}")


def seeded_sequence(seed: int, index: int, seq_len: int, vocab: int,
                    median: float = 700.0, sigma: float = 1.2
                    ) -> Dict[str, np.ndarray]:
    """Sequence ``index`` of the seeded stream."""
    rng = np.random.default_rng([seed, index, 0x70C5])
    lengths, total = [], 0
    while total < seq_len:
        length = int(np.clip(rng.lognormal(np.log(median), sigma), 1,
                             seq_len))
        lengths.append(length)
        total += length
    segment_ids, positions = pack_documents(lengths, seq_len)
    tokens = rng.integers(0, vocab, seq_len, dtype=np.int32)
    return {"tokens": tokens, "segment_ids": segment_ids,
            "positions": positions}


class TokenLoader:
    """Batches of packed sequences. One ``__iter__`` pass yields the rest
    of the current epoch from the cursor (a seeded stream's epoch is
    ``sequences_per_epoch`` long and each epoch draws afresh)."""

    def __init__(self, batch_size: int, seq_len: int, vocab: int,
                 seed: int = 0, token_file: Optional[str] = None,
                 sequences_per_epoch: int = 1 << 20):
        self.batch_size, self.seq_len, self.vocab = batch_size, seq_len, vocab
        self.seed, self.epoch, self._pos = int(seed), 0, 0
        self._tokens = self._offsets = None
        if token_file is not None:
            with np.load(token_file) as data:
                self._tokens = np.asarray(data["tokens"], np.int32)
                self._offsets = np.asarray(data["offsets"], np.int64)
            if self._tokens.max(initial=0) >= vocab or \
                    self._tokens.min(initial=0) < 0:
                raise ValueError(
                    f"{token_file}: ids outside the {vocab} rows of the "
                    "vocabulary held")
            sequences_per_epoch = len(self._tokens) // seq_len
        self.sequences_per_epoch = sequences_per_epoch
        if sequences_per_epoch < batch_size:
            raise ValueError("fewer sequences than one batch")

    def __len__(self) -> int:
        return self.sequences_per_epoch // self.batch_size

    def _sequence(self, index: int) -> Dict[str, np.ndarray]:
        if self._tokens is None:
            return seeded_sequence(
                self.seed, self.epoch * self.sequences_per_epoch + index,
                self.seq_len, self.vocab)
        lo = index * self.seq_len
        hi = lo + self.seq_len
        starts = self._offsets[(self._offsets > lo) & (self._offsets < hi)]
        edges = np.concatenate([[lo], starts, [hi]])
        segment_ids, positions = pack_documents(np.diff(edges),
                                                self.seq_len)
        return {"tokens": self._tokens[lo:hi], "segment_ids": segment_ids,
                "positions": positions}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while self._pos + self.batch_size <= self.sequences_per_epoch:
            rows = [self._sequence(self._pos + i)
                    for i in range(self.batch_size)]
            # the cursor moves when the batch is handed over, so a
            # snapshot names exactly the sequences trained on
            self._pos += self.batch_size
            yield {k: np.stack([r[k] for r in rows]) for k in rows[0]}
        self.epoch += 1
        self._pos = 0

    def state(self) -> LoaderState:
        return LoaderState(seed=self.seed, epoch=self.epoch, pos=self._pos)

    def load_state(self, state) -> None:
        if isinstance(state, dict):
            state = LoaderState.from_dict(state)
        if state.pos % self.batch_size:
            raise ValueError(
                f"loader cursor {state.pos} is not a multiple of "
                f"batch_size={self.batch_size}")
        self.seed, self.epoch, self._pos = (int(state.seed),
                                            int(state.epoch),
                                            int(state.pos))
