"""The training job's token feed: a Zipf corpus made from the seed.

A copy of the scheme of ``repro.data.pipeline.SyntheticTokens`` (batches are
a pure function of (seed, step), Zipf exponent and vocabulary from the
traffic file), kept here so that the yardstick cannot move with the program.
It keeps the pipeline protocol the training loop calls: ``next``,
``save_state`` and ``restore_state`` through the job's file system.

The harness ends the job through this feed: ``next`` returns ``None`` once
``stop`` is set, which ends ``repro.train.loop.train``'s step loop without a
further step or save.  Each call is also a hook for the window's clock.
"""
from __future__ import annotations

import json
from typing import Callable, Optional

import numpy as np

STATE_PATH = "/datapipe.json"


class ZipfTokens:
    def __init__(self, vocab: int, batch: int, seq: int, *, seed: int,
                 zipf_a: float, on_next: Optional[Callable[[int], None]] = None,
                 on_state: Optional[Callable[[str, bool], None]] = None):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.seed = seed
        self.zipf_a = zipf_a
        self.step = 0
        self.stop = False
        self.on_next = on_next        # called with the step index handed out
        self.on_state = on_state      # called with (span, entering)
        self.restored_steps: list[int] = []

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        z = rng.zipf(self.zipf_a, size=(self.batch, self.seq))
        return {"tokens": (z % (self.vocab - 2)).astype(np.int32) + 1}

    def next(self) -> Optional[dict]:
        if self.stop:
            return None
        out = self.batch_at(self.step)
        if self.on_next is not None:
            self.on_next(self.step)
        self.step += 1
        return out

    def _span(self, entering: bool) -> None:
        if self.on_state is not None:
            self.on_state("pipeline_state", entering)

    def save_state(self, fs, path: str = STATE_PATH) -> None:
        self._span(True)
        blob = json.dumps({"seed": self.seed, "step": self.step}).encode()
        fd = fs.open(path)
        fs.pwrite(fd, blob.ljust(256), 0)
        fs.close(fd)
        self._span(False)

    def restore_state(self, fs, path: str = STATE_PATH) -> bool:
        self._span(True)
        fd = fs.open(path)
        raw = fs.pread(fd, 256, 0)
        fs.close(fd)
        self._span(False)
        if not raw.strip():
            return False
        st = json.loads(raw.decode())
        if st["seed"] != self.seed:
            raise ValueError(f"corpus seed mismatch: saved {st['seed']}, "
                             f"this feed {self.seed}")
        self.step = st["step"]
        self.restored_steps.append(self.step)
        return True
