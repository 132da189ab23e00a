"""Fixed-length windows over a flat file of int32 token ids.

``iter = tokens`` reads ``path_tokens`` (little-endian int32, one id
after the other; ``.npy`` arrays of integers are read too) and cuts it
into windows of ``seq_len + 1`` ids that overlap by one: a window's first
``seq_len`` ids are the data row ``(seq_len,)`` int32, the ids one
position later are its ``seq_len`` labels (float32, like every label
field), so a net with ``label_vec[0,seq_len) = label`` learns the next
token at every position. One document a window: nothing is packed or
masked. ``shuffle = 1`` permutes the windows each epoch from
``seed_data``; full batches only (the tail is dropped, as ``mnist``
does). ``nvocab`` (optional) checks the ids against the vocabulary slice
the net holds.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .data import DataBatch, IIterator, resolve_data_shard


def read_tokens(path: str) -> np.ndarray:
    from ..utils.stream import open_stream
    with open_stream(path, "rb") as f:
        if path.endswith(".npy"):
            ids = np.load(f)
            if not np.issubdtype(ids.dtype, np.integer):
                raise ValueError("tokens: %s holds %s, not integers"
                                 % (path, ids.dtype))
            return np.ascontiguousarray(ids.reshape(-1), np.int32)
        return np.frombuffer(f.read(), "<i4")


class TokenIterator(IIterator):
    def __init__(self):
        self.silent = 0
        self.batch_size = 0
        self.seq_len = 0
        self.shuffle = 0
        self.seed = 0
        self.nvocab = 0
        self.path = ""
        self.part_index = 0
        self.num_parts = 1
        self.epoch = 0
        self.loc = 0
        self.out: Optional[DataBatch] = None

    def set_param(self, name: str, val: str) -> None:
        if name == "silent":
            self.silent = int(val)
        if name == "batch_size":
            self.batch_size = int(val)
        if name == "seq_len":
            self.seq_len = int(val)
        if name == "input_shape" and not self.seq_len:
            self.seq_len = int(val.split(",")[-1])
        if name == "shuffle":
            self.shuffle = int(val)
        if name == "seed_data":
            self.seed = int(val)
        if name == "nvocab":
            self.nvocab = int(val)
        if name == "path_tokens":
            self.path = val
        if name == "part_index":
            self.part_index = int(val)
        if name == "num_parts":
            self.num_parts = int(val)

    def init(self) -> None:
        assert self.batch_size > 0, "tokens iterator: batch_size not set"
        assert self.seq_len > 0, "tokens iterator: seq_len not set"
        ids = read_tokens(self.path)
        if self.nvocab and ids.size and (ids.min() < 0
                                         or ids.max() >= self.nvocab):
            raise ValueError(
                "tokens: ids of %s lie in [%d, %d], outside the %d rows "
                "the net holds" % (self.path, ids.min(), ids.max(),
                                   self.nvocab))
        n = (ids.size - 1) // self.seq_len
        if n < self.batch_size:
            raise ValueError("tokens: %s holds %d windows of %d, under one "
                             "batch of %d" % (self.path, n, self.seq_len,
                                              self.batch_size))
        self.ids = ids
        starts = np.arange(n, dtype=np.int64) * self.seq_len
        pi, nparts = resolve_data_shard(self.part_index, self.num_parts)
        self.starts = starts[pi::nparts] if nparts > 1 else starts
        self.before_first()
        self.epoch = 0
        if self.silent == 0:
            print("TokenIterator: %d ids, %d windows of %d, shuffle=%d"
                  % (ids.size, n, self.seq_len, self.shuffle))

    def before_first(self) -> None:
        self.loc = 0
        self.order = self.starts
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            self.order = self.starts[rng.permutation(self.starts.size)]
        self.epoch += 1

    def next(self) -> bool:
        b = self.batch_size
        if self.loc + b > self.order.size:
            return False
        at = self.order[self.loc:self.loc + b]
        rows = self.ids[at[:, None] + np.arange(self.seq_len + 1)[None, :]]
        self.out = DataBatch(
            data=np.ascontiguousarray(rows[:, :-1], np.int32),
            label=rows[:, 1:].astype(np.float32),
            inst_index=(at // self.seq_len).astype(np.uint32))
        self.loc += b
        return True

    def value(self) -> DataBatch:
        return self.out
