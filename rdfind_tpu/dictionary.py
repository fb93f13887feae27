"""Host-side string interning: the global value dictionary.

The reference compresses long values with a hash dictionary and escapes collisions
(operators/CreateHashes.scala, util/HashCollisionHandler.scala:11-42, plus the
CheckHashCollisions oracle program).  The TPU build instead interns every string
exactly once into dense int32 ids — exact (no collision handling needed, subsuming
CreateHashes/CombineHashes/ConditionCompressor/ConditionDecompressor) and the natural
device representation: all downstream compute is on int32 tables.

One dictionary spans all three triple fields, because join lines group captures by
shared *value* across fields (RDFind.scala:332-346 groups JoinCandidates by the raw
string join value).
"""

from __future__ import annotations

import zlib

import numpy as np


def value_shard(value, num_shards: int) -> int:
    """THE hash-partition function for sharded interning.

    Every layer that splits a dictionary by value must agree on one partition
    or merged ids diverge: the multi-host hash-partitioned interning
    (runtime/multihost_ingest._value_owner), and the native parallel ingest's
    per-thread interner merge (native/rdfind_native.cpp, crc32 % S) both use
    exactly this: crc32 over the UTF-8 bytes, mod the shard count.
    """
    return zlib.crc32(str(value).encode("utf-8")) % num_shards


class Dictionary:
    """Sorted unique values; id = rank in sorted order."""

    def __init__(self, values: np.ndarray):
        self.values = values  # sorted 1-D array of str/bytes

    def __len__(self) -> int:
        return len(self.values)

    def __eq__(self, other) -> bool:
        if not isinstance(other, Dictionary):
            return NotImplemented
        return len(self) == len(other) and bool(
            np.all(self.values == other.values))

    def value(self, idx: int):
        return self.values[idx]

    def id(self, value) -> int:
        """Exact lookup; raises KeyError for unknown values."""
        pos = int(np.searchsorted(self.values, value))
        if pos >= len(self.values) or self.values[pos] != value:
            raise KeyError(value)
        return pos

    def ids(self, values) -> np.ndarray:
        values = np.asarray(values)
        pos = np.searchsorted(self.values, values)
        pos_clip = np.minimum(pos, len(self.values) - 1)
        if not np.all(self.values[pos_clip] == values):
            raise KeyError("unknown value(s) in lookup")
        return pos_clip.astype(np.int32)


def blob_is_lossless(blob: np.ndarray, offsets: np.ndarray) -> bool:
    """Whether each value of a UTF-8 blob decodes by itself.

    `blob` (uint8) holds the values back to back, value i at
    blob[offsets[i]:offsets[i + 1]].  An ASCII blob always does.  Otherwise
    the blob must decode whole and every value start on a character, not
    on a continuation byte (0b10xxxxxx): an invalid suffix of one value can
    splice with an invalid prefix of the next into a valid sequence
    (b"\\xc3" + b"\\xa9" == "é"), which the whole decode alone would pass.
    """
    if not len(blob) or blob.max() < 0x80:
        return True
    try:
        str(blob, "utf-8")
    except UnicodeDecodeError:
        return False
    starts = np.asarray(offsets[:-1])
    starts = starts[starts < len(blob)]
    return not ((blob[starts] & 0xC0) == 0x80).any()


class BlobDictionary(Dictionary):
    """A Dictionary kept as the native ingest's sorted UTF-8 value blob.

    Building one Python string per distinct value costs about a
    microsecond each, while a discovery reads only the few values its
    output prints.  So a value is decoded when `value` asks for it, and
    `values` decodes them all once, for the callers that need the array
    (checkpoints, delta bundles, the serving index, id lookups).  The
    caller vouches for `blob_is_lossless(blob, offsets)`.  `decoded`
    counts the values decoded one at a time.
    """

    def __init__(self, blob: np.ndarray, offsets: np.ndarray):
        self._blob = blob
        self._offsets = offsets
        self._values = None
        self.decoded = 0

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            text = str(self._blob, "utf-8")
            chars = np.asarray(self._offsets, np.int64)
            if len(text) != len(self._blob):
                # A value's characters lie between its byte offsets less
                # the continuation bytes before them.
                cont = (self._blob & 0xC0) == 0x80
                before = np.concatenate([[0], np.cumsum(cont, dtype=np.int64)])
                chars = chars - before[chars]
            bounds = chars.tolist()
            values = np.empty(len(self), object)
            values[:] = [text[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
            self._values = values
        return self._values

    def __len__(self) -> int:
        return len(self._offsets) - 1

    def value(self, idx: int):
        if self._values is not None:
            return self._values[idx]
        i = range(len(self))[idx]  # an array's negative and IndexError
        self.decoded += 1
        return str(self._blob[self._offsets[i]:self._offsets[i + 1]], "utf-8")


def intern_triples(triples) -> tuple[np.ndarray, Dictionary]:
    """Intern an iterable/array of (s, p, o) values into an (N, 3) int32 id table."""
    arr = np.asarray(triples)
    if arr.size == 0:
        return np.zeros((0, 3), np.int32), Dictionary(np.zeros(0, object))
    if arr.ndim != 2 or arr.shape[1] != 3:
        raise ValueError(f"expected (N, 3) triples, got shape {arr.shape}")
    uniques, inverse = np.unique(arr.reshape(-1), return_inverse=True)
    if len(uniques) >= np.iinfo(np.int32).max:
        raise ValueError("dictionary exceeds int32 id space")
    ids = inverse.reshape(arr.shape).astype(np.int32)
    return ids, Dictionary(uniques)
