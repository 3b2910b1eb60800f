"""Request bodies, made from ``--seed`` and the request's counter. Every body
in a window is unique bytes, so no cache can answer for the device."""

from __future__ import annotations

import io
import json

import numpy as np

POOL = 8    # base tiles held in memory; a flood window may send 10^4 tiles


class TilePayloads:
    """rgb8 ``.npy`` tiles: a seeded pool of base tiles with the request's
    counter stamped into the first eight pixel bytes."""

    content_type = "application/octet-stream"

    def __init__(self, seed: int, tile: int, reference_bases: int = POOL,
                 **_):
        rng = np.random.default_rng([seed, 0x711E])
        self.tile = tile
        self.reference_bases = reference_bases
        self._base = []
        for _i in range(POOL):
            buf = io.BytesIO()
            # Each base tile has its own brightness and contrast per
            # channel, so different tiles give visibly different class
            # histograms and the reference check can tell them apart.
            mean = rng.uniform(40, 215, size=3)
            amp = rng.uniform(5, 60, size=3)
            noise = rng.standard_normal((tile, tile, 3))
            np.save(buf, np.clip(mean + amp * noise, 0, 255).astype(np.uint8))
            self._base.append(buf.getvalue())
        self._offset = len(self._base[0]) - tile * tile * 3

    def body(self, counter: int) -> bytes:
        raw = bytearray(self._base[counter % POOL])
        raw[self._offset:self._offset + 8] = int(counter).to_bytes(8, "little")
        return bytes(raw)

    def array(self, counter: int) -> np.ndarray:
        return np.load(io.BytesIO(self.body(counter)))

    def valid(self, result, arrival=None) -> bool:
        """Every pixel of the tile was classified."""
        hist = (result or {}).get("class_histogram")
        return (isinstance(hist, dict)
                and sum(int(v) for v in hist.values()) == self.tile ** 2)

    def eligible(self, record: dict) -> bool:
        """The reference computes the first ``reference_bases`` base tiles
        while the worker warms up; tiles of the others are served and
        counted, not sampled for the check."""
        return record["counter"] % POOL < self.reference_bases


class PromptPayloads:
    """Token-id prompts: ids drawn from ``(seed, counter)``; the lengths come
    from the generator's schedule."""

    content_type = "application/json"

    def __init__(self, seed: int, vocab_size: int,
                 reference_max_len: int = 512, **_):
        self.seed = seed
        self.vocab = vocab_size
        self.reference_max_len = reference_max_len

    def prompt(self, counter: int, length: int) -> list[int]:
        rng = np.random.default_rng([self.seed, 0x9707, counter])
        return rng.integers(0, self.vocab, size=length).tolist()

    def body(self, counter: int, length: int, max_new: int) -> bytes:
        return json.dumps({"prompt": self.prompt(counter, length),
                           "max_new_tokens": max_new}).encode()


    def valid(self, result, arrival=None) -> bool:
        """No end-of-sequence id is configured and prompt + output fits the
        cache, so a stream returns exactly the tokens it asked for."""
        tokens = (result or {}).get("tokens")
        return (isinstance(tokens, list) and arrival is not None
                and len(tokens) == result.get("count")
                == arrival["max_new_tokens"]
                and all(isinstance(t, int) and 0 <= t < self.vocab
                        for t in tokens))

    def eligible(self, record: dict) -> bool:
        """The reference is teacher-forced at one padded length; streams
        longer than it are served and timed, not sampled for the check."""
        return (record["prompt_len"] + record["max_new_tokens"]
                <= self.reference_max_len)


KINDS = {"tile": TilePayloads, "prompt": PromptPayloads}
