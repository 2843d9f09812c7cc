"""Simulated device driver: DRAM address space + control handshake.

Models the pieces of the FPGA platform the runtime needs (§3.2): a
physically-contiguous DRAM allocator (VTABufferAlloc), typed load/store
views for DMA, and the fetch-module control registers (§2.4: `control`,
`insn_count`, `insns`).  On real hardware these are AXI/MMIO; here they
drive the behavioural simulator.  Cache flush/invalidate (non-coherent
SoCs) are modelled as hooks with counters so the runtime code path
stays faithful and testable; a flush also marks the flushed range as
written (see :meth:`Dram.touch`).
"""
from __future__ import annotations

import hashlib
import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

#: log2 of the page size whose write epochs a Dram keeps (4 KiB pages)
PAGE_SHIFT = 12

# write stamps, unique across every Dram of the process: a page's epoch
# is the stamp of the last write that touched it, and clone / copy_from
# carry the epochs with the bytes.  So two ranges whose newest epoch is
# the same stamp hold the same bytes (both are the writer's range as it
# was after that write, or zeros for stamp 0), and one memo of region
# digests serves every Dram: keyed by (addr, nbytes, newest epoch),
# shared under _DIGEST_LOCK, LRU order, bounded by entry count.
_STAMPS = itertools.count(1)
_DIGESTS: Dict[tuple, bytes] = {}
_DIGEST_LOCK = threading.Lock()
_DIGESTS_CAP = 1 << 16


class Dram:
    """Flat byte-addressed DRAM with a bump allocator (physically contiguous
    buffers, as required by VTA's DMA engines).  Every write path marks
    the pages it changes with a fresh epoch, so :meth:`region_digest` can
    name a range's content without reading it again."""

    #: the address of ``mem[0]`` (nonzero for a window onto part of an
    #: address space)
    lo = 0

    def __init__(self, size: int = 1 << 28, align: int = 64):
        self.size = size
        self.align = align
        self.mem = np.zeros(size, dtype=np.uint8)
        self.epochs = np.zeros(-(-size >> PAGE_SHIFT), dtype=np.int64)
        self._next = align  # keep address 0 as a null sentinel
        self._allocs: Dict[int, int] = {}

    def alloc(self, nbytes: int, align: int | None = None) -> int:
        a = max(self.align, align or 1)
        addr = (self._next + a - 1) // a * a
        if addr + nbytes > self.size:
            raise MemoryError(f"DRAM exhausted: {addr + nbytes} > {self.size}")
        self._next = addr + nbytes
        self._allocs[addr] = nbytes
        return addr

    def free(self, addr: int) -> None:
        self._allocs.pop(addr, None)  # bump allocator: bookkeeping only

    def clone(self, trim: bool = False) -> "Dram":
        """Independent copy of the DRAM state.  With ``trim`` the copy
        keeps only the ALLOCATED image (every byte below the bump
        pointer, rounded up to alignment): reads and writes of existing
        buffers behave identically, but any further ``alloc`` raises
        MemoryError — exactly the contract of a pooled serving device,
        whose pre-staged CompiledProgram must never allocate per call.
        A pool of trimmed clones costs O(used bytes) each instead of the
        full address-space image."""
        c = Dram.__new__(Dram)
        c.align = self.align
        if trim:
            used = (self._next + self.align - 1) // self.align * self.align
            c.size = used
            c.mem = self.mem[:used].copy()
            c.epochs = self.epochs[:-(-used >> PAGE_SHIFT)].copy()
        else:
            c.size = self.size
            c.mem = self.mem.copy()
            c.epochs = self.epochs.copy()
        c._next = self._next
        c._allocs = dict(self._allocs)
        return c

    def copy_from(self, other: "Dram") -> None:
        """Adopt another DRAM's full state (same-size images only)."""
        if other.size != self.size:
            raise ValueError(f"DRAM size mismatch: {other.size} != {self.size}")
        self.mem[:] = other.mem
        self.epochs[:] = other.epochs
        self._next = other._next
        self._allocs = dict(other._allocs)

    # -- write epochs and content digests ----------------------------------
    def touch(self, addr: int, nbytes: int) -> None:
        """Mark ``[addr, addr + nbytes)`` as written: its pages take a
        fresh epoch.  Every write path calls it; code that changes
        ``mem`` directly calls it through ``Device.flush_cache``."""
        if nbytes > 0:
            a = addr - self.lo
            self.epochs[a >> PAGE_SHIFT:
                        ((a + nbytes - 1) >> PAGE_SHIFT) + 1] = next(_STAMPS)

    def region_digest(self, addr: int, nbytes: int) -> bytes:
        """SHA-1 digest of the bytes ``[addr, addr + nbytes)``: equal
        digests mean equal bytes.  Memoized process-wide by the range
        and its newest page epoch, so the bytes are hashed again only
        after a write touched the range, and clones of one image hash
        each range once between them."""
        a = addr - self.lo
        if a < 0 or a + nbytes > self.mem.size:
            raise IndexError(f"DRAM range [{addr}, {addr + nbytes}) is "
                             f"outside [{self.lo}, {self.lo + self.mem.size})")
        stamp = int(self.epochs[a >> PAGE_SHIFT:
                                ((a + nbytes - 1) >> PAGE_SHIFT) + 1]
                    .max(initial=0))
        key = (addr, nbytes, stamp)
        with _DIGEST_LOCK:
            d = _DIGESTS.pop(key, None)
            if d is not None:
                _DIGESTS[key] = d       # re-insert: LRU order by last hit
                return d
        d = hashlib.sha1(self.mem[a:a + nbytes]).digest()
        with _DIGEST_LOCK:
            while len(_DIGESTS) >= _DIGESTS_CAP:
                _DIGESTS.pop(next(iter(_DIGESTS)))
            _DIGESTS[key] = d
        return d

    # -- typed access ---------------------------------------------------
    def write(self, addr: int, arr: np.ndarray) -> None:
        b = np.ascontiguousarray(arr).view(np.uint8).ravel()
        a = addr - self.lo
        self.mem[a:a + b.size] = b
        self.touch(addr, b.size)

    def read(self, addr: int, nbytes: int, dtype=np.uint8, shape=None) -> np.ndarray:
        a = addr - self.lo
        raw = self.mem[a:a + nbytes]
        out = raw.view(dtype).copy()
        return out.reshape(shape) if shape is not None else out


@dataclass
class ControlRegisters:
    """fetch-module MMIO registers (§2.4)."""
    control: int = 0       # bit0 = start, bit1 = done
    insn_count: int = 0
    insns: int = 0         # DRAM physical address of the instruction stream

    def start(self) -> None:
        self.control |= 1
        self.control &= ~2

    def set_done(self) -> None:
        self.control &= ~1
        self.control |= 2

    @property
    def done(self) -> bool:
        return bool(self.control & 2)


class Device:
    """One simulated VTA device: DRAM + control registers + cache model."""

    def __init__(self, dram_size: int = 1 << 28):
        self.dram = Dram(dram_size)
        self.regs = ControlRegisters()
        self.cache_flushes = 0
        self.cache_invalidates = 0

    def clone(self, trim: bool = False) -> "Device":
        """Independent copy of the full device state — the cross-backend
        checker runs each engine against its own clone and diffs the
        resulting DRAM images.  ``trim`` clones only the allocated DRAM
        image and forbids further allocation (see :meth:`Dram.clone`) —
        the device-pool slot configuration."""
        c = Device.__new__(Device)
        c.dram = self.dram.clone(trim=trim)
        c.regs = ControlRegisters(self.regs.control, self.regs.insn_count,
                                  self.regs.insns)
        c.cache_flushes = self.cache_flushes
        c.cache_invalidates = self.cache_invalidates
        return c

    def copy_from(self, other: "Device") -> None:
        """Adopt another device's state (used to fold a checker clone's
        results back into the runtime's live device)."""
        self.dram.copy_from(other.dram)
        self.regs.control = other.regs.control
        self.regs.insn_count = other.regs.insn_count
        self.regs.insns = other.regs.insns
        self.cache_flushes = other.cache_flushes
        self.cache_invalidates = other.cache_invalidates

    def stage_stream(self, stream: np.ndarray) -> int:
        """DMA an encoded instruction stream to DRAM and kick the fetch
        registers (§2.4) — the shared handshake every execution engine
        performs before running to FINISH.  Returns the stream address."""
        addr = self.dram.alloc(stream.nbytes)
        self.dram.write(addr, stream)
        self.kick_stream(addr, stream.shape[0])
        return addr

    def kick_stream(self, addr: int, insn_count: int) -> None:
        """Point the fetch registers at an ALREADY-staged instruction
        stream and start the engine — the repeat-call handshake of a
        pre-staged CompiledProgram (zero per-call DRAM allocation)."""
        self.regs.insns = addr
        self.regs.insn_count = insn_count
        self.regs.start()

    # non-coherent-SoC cache maintenance hooks (§3.2)
    def flush_cache(self, addr: int, nbytes: int) -> None:
        """The host wrote ``[addr, addr + nbytes)``: count the flush and
        give the range's pages a fresh write epoch."""
        self.cache_flushes += 1
        self.dram.touch(addr, nbytes)

    def invalidate_cache(self, addr: int, nbytes: int) -> None:
        self.cache_invalidates += 1
