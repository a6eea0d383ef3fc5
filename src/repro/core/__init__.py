"""NVCache core — the paper's contribution (user-space NVMM write-back
cache with synchronous durability and durable linearizability)."""
from repro.core.api import NVCache, O_APPEND, O_CREAT, O_RDONLY, O_RDWR, O_TRUNC, O_WRONLY
from repro.core.log import EntryRef, NVLog
from repro.core.namespace import Namespace
from repro.core.nvmm import NVMM
from repro.core.policy import TEST_SMALL, Policy
from repro.core.recovery import RecoveryStats, recover
from repro.core.router import EpochRouter

__all__ = [
    "NVCache", "NVLog", "NVMM", "Namespace", "EntryRef", "EpochRouter",
    "Policy", "TEST_SMALL", "RecoveryStats", "recover",
    "O_RDONLY", "O_WRONLY", "O_RDWR", "O_CREAT", "O_APPEND", "O_TRUNC",
]
