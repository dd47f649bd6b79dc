"""Memory-mapped token-stream loader for LM training (OpenWebText-style corpora).

Reference capability being matched (not ported):
  * OpenWebTextDataLoader — include/data_loading/open_webtext_data_loader.hpp:11-45 —
    mmap'd uint16 token file; batches are (B, S) windows with next-token labels.

TPU-first differences: labels are int32 token ids, NOT one-hot (B,S,V) floats — the
reference materializes 50257-wide one-hot label tensors per batch, which at bs=8, S=1024
is 1.6 GB of mostly-zero floats per batch; integer labels plus a fused
softmax-cross-entropy on device do the same job at 1/50257th the bytes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..profiling.profiler import span
from .loader import DataLoader


class TokenStreamDataLoader(DataLoader):
    """(B, S) windows over a flat token file, with shifted next-token labels."""

    def __init__(self, path: str, context_length: int, dtype=np.uint16, seed: int = 0,
                 pad_token_id: Optional[int] = None):
        from .. import native

        super().__init__(seed)
        self.tokens = np.memmap(path, dtype=dtype, mode="r")
        # threaded native window reads when the runtime is built (same mmap
        # underneath; output is identical); dtypes the native reader doesn't
        # speak, or any init failure, silently keep the numpy path
        self._native_tokens = None
        if native.available() and np.dtype(dtype) in (np.uint16, np.int32,
                                                      np.uint32):
            try:
                self._native_tokens = native.api.TokenFile(path, dtype)
            except (ValueError, OSError):
                self._native_tokens = None
        self.context_length = int(context_length)
        self.pad_token_id = pad_token_id
        # valid window starts are 0..L-S-1 (each needs S tokens + 1 label lookahead)
        self._num_samples = max(0, len(self.tokens) - self.context_length)
        self._data_shape = (self.context_length,)
        self._label_shape = (self.context_length,)

    def _get(self, indices: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        S = self.context_length
        if self._native_tokens is not None:
            full = self._native_tokens.windows(np.asarray(indices, np.int64), S + 1)
            # views into the freshly assembled buffer; masking labels in place
            # would also hit data (they overlap in `full`), so copy only then
            data, labels = full[:, :-1], full[:, 1:]
            if self.pad_token_id is not None:
                labels = labels.copy()
        else:
            data = np.empty((len(indices), S), np.int32)
            labels = np.empty((len(indices), S), np.int32)
            for b, start in enumerate(indices):
                window = np.asarray(self.tokens[start:start + S + 1], np.int32)
                data[b] = window[:-1]
                labels[b] = window[1:]
        if self.pad_token_id is not None:
            # loss masks these out (losses.softmax_cross_entropy ignore_index)
            labels[labels == self.pad_token_id] = -1
        return data, labels

    def random_windows(self, batch_size: int, rng: Optional[np.random.Generator] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Uniformly random windows — the shuffled-sampling mode of the reference
        loader (open_webtext_data_loader.hpp:32-35) without epoch bookkeeping."""
        if self._num_samples == 0:
            raise ValueError(
                f"token file has {len(self.tokens)} tokens — too short for "
                f"context_length={self.context_length} (need at least "
                f"{self.context_length + 1})")
        rng = rng or self._rng
        with span("train.input", rows=batch_size):    # the window draw
            starts = rng.integers(0, self._num_samples, batch_size)
            return self._get(starts)


class OpenWebTextDataLoader(TokenStreamDataLoader):
    """uint16 OpenWebText .bin produced by a tiktoken GPT-2 encoding pass
    (reference corpus prep: python/openwebtext.py)."""

    def __init__(self, path: str, context_length: int = 1024, seed: int = 0):
        super().__init__(path, context_length, dtype=np.uint16, seed=seed)
