"""Offset checkpointing with the reference's file semantics.

reference: datax-host checkpoint/EventhubCheckpointer.scala:13-74 —
``offsets.txt`` holds one line per partition
``<ts>,<source>,<partition>,<fromSeq>,<untilSeq>``; before each write the
previous file is copied to ``offsets.txt.old``; on (re)start offsets are
read (falling back to the .old backup) and applied as starting positions.
At-least-once: a crash between sink write and checkpoint replays the
last batch.
"""

from __future__ import annotations

import os
import shutil
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from ..obs.tracing import span as _trace_span


def _durable_replace(tmp: str, dst: str) -> None:
    """``os.replace`` with power-loss durability: fsync the temp file
    before the rename (data hits the platter, not just the page cache)
    and fsync the directory after it (the rename itself is a directory
    entry). Without both, a crash-then-power-loss can surface a zero
    -length or missing checkpoint even though the process "wrote" it."""
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, dst)
    dir_fd = os.open(os.path.dirname(dst) or ".", os.O_RDONLY)
    try:
        os.fsync(dir_fd)
    finally:
        os.close(dir_fd)


def snapshot_arrays(snap: Dict) -> Dict:
    """Flatten a window-state snapshot dict
    (``FlowProcessor.snapshot_window_state`` shape) into the named
    numpy arrays one ``np.savez`` call persists. Shared by the
    whole-file checkpoint below and the per-partition payloads the
    state-partition stores ship (runtime/statepartition.py)."""
    import json as _json

    import numpy as np

    arrays: Dict = {}
    for table, ring in snap.get("rings", {}).items():
        for c, a in ring["cols"].items():
            arrays[f"ring/{table}/col/{c}"] = a
        arrays[f"ring/{table}/valid"] = ring["valid"]
        if ring.get("cap") is not None:
            # compacted partition snapshots carry the original ring
            # capacity so the merge can rebuild the full shape
            arrays[f"ring/{table}/cap"] = np.asarray(
                int(ring["cap"]), np.int64
            )
    for view, part in snap.get("partials", {}).items():
        # the head of a view's per-slot partial aggregates: its key
        # directory and slot times. The slots' rows are not here: whole
        # in a partition payload ("parts"), in slot files a checkpoint
        for i, a in enumerate(part["keys"]):
            arrays[f"partial/{view}/key/{i}"] = a
        for field_ in ("used", "slot_ts", "slot_live", "slot_gen"):
            arrays[f"partial/{view}/{field_}"] = part[field_]
    arrays["slot_counter"] = np.asarray(int(snap.get("slot_counter", 0)),
                                        np.int64)
    base = snap.get("base_ms")
    arrays["base_ms"] = np.asarray(-1 if base is None else int(base),
                                   np.int64)
    if snap.get("dictionary") is not None:
        # ring ids are meaningless without the dictionary that encoded
        # them; ride it along as JSON bytes
        arrays["dictionary_json"] = np.frombuffer(
            _json.dumps(snap["dictionary"]).encode("utf-8"), dtype=np.uint8
        )
    return arrays


def arrays_to_snapshot(z) -> Dict:
    """Inverse of ``snapshot_arrays`` over a loaded npz mapping."""
    import json as _json

    rings: Dict[str, Dict] = {}
    for key in z.files:
        if not key.startswith("ring/"):
            continue
        _, table, kind = key.split("/", 2)
        ring = rings.setdefault(table, {"cols": {}, "valid": None})
        if kind == "valid":
            ring["valid"] = z[key]
        elif kind == "cap":
            ring["cap"] = int(z[key])
        else:
            ring["cols"][kind.split("/", 1)[1]] = z[key]
    partials: Dict[str, Dict] = {}
    for key in z.files:
        if not key.startswith("partial/"):
            continue
        _, view, kind = key.split("/", 2)
        part = partials.setdefault(view, {"keys": {}})
        if kind.startswith("key/"):
            part["keys"][int(kind[4:])] = z[key]
        elif kind in ("used", "slot_ts", "slot_live", "slot_gen"):
            part[kind] = z[key]
    for part in partials.values():
        part["keys"] = [part["keys"][i] for i in sorted(part["keys"])]
    base = int(z["base_ms"])
    out = {
        "rings": rings,
        "slot_counter": int(z["slot_counter"]),
        "base_ms": None if base < 0 else base,
    }
    if "dictionary_json" in z.files:
        out["dictionary"] = _json.loads(
            z["dictionary_json"].tobytes().decode("utf-8")
        )
    if partials:
        out["partials"] = partials
    return out


@dataclass(frozen=True)
class PartitionOffset:
    ts_ms: int
    source: str
    partition: int
    from_seq: int
    until_seq: int


class OffsetCheckpointer:
    FILE = "offsets.txt"
    BACKUP = "offsets.txt.old"

    def __init__(self, checkpoint_dir: str):
        self.dir = checkpoint_dir
        os.makedirs(checkpoint_dir, exist_ok=True)

    @property
    def path(self) -> str:
        return os.path.join(self.dir, self.FILE)

    @property
    def backup_path(self) -> str:
        return os.path.join(self.dir, self.BACKUP)

    def write_offsets(self, offsets: List[PartitionOffset]) -> None:
        """Backup then write, as the reference does (scala :43-61) —
        fsynced so the checkpoint survives power loss, not just a
        process crash."""
        if os.path.exists(self.path):
            shutil.copyfile(self.path, self.backup_path)
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for o in offsets:
                f.write(
                    f"{o.ts_ms},{o.source},{o.partition},{o.from_seq},{o.until_seq}\n"
                )
            f.flush()
            os.fsync(f.fileno())
        _durable_replace(tmp, self.path)

    def read_offsets(self) -> List[PartitionOffset]:
        """Read current file, falling back to the backup (scala :63-73)."""
        for path in (self.path, self.backup_path):
            if os.path.exists(path):
                try:
                    return self._parse(path)
                except Exception:
                    continue
        return []

    @staticmethod
    def _parse(path: str) -> List[PartitionOffset]:
        out = []
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                ts, source, part, from_seq, until_seq = line.split(",")
                out.append(
                    PartitionOffset(
                        int(ts), source, int(part), int(from_seq), int(until_seq)
                    )
                )
        return out

    def starting_positions(self) -> Dict[Tuple[str, int], int]:
        """(source, partition) -> next sequence number to read."""
        return {
            (o.source, o.partition): o.until_seq for o in self.read_offsets()
        }

    def checkpoint_batch(
        self, consumed: Dict[Tuple[str, int], Tuple[int, int]]
    ) -> None:
        """consumed: (source, partition) -> (from_seq, until_seq)."""
        with _trace_span("checkpoint/offsets"):
            now = int(time.time() * 1000)
            merged: Dict[Tuple[str, int], PartitionOffset] = {
                (o.source, o.partition): o for o in self.read_offsets()
            }
            for (source, part), (from_seq, until_seq) in consumed.items():
                merged[(source, part)] = PartitionOffset(
                    now, source, part, from_seq, until_seq
                )
            self.write_offsets(list(merged.values()))


def _slot_entries(
    files: List, slots: int, counter: int
) -> List[Tuple[int, int, str, int]]:
    """[slot, generation, file, row] a slot still held, from a head of
    PR 32's form: [first generation, last generation, file] a file, the
    batch of counter g in slot g mod ``slots`` and in row g - first of
    its file (one batch wrote a slot, so its counter is the slot's
    generation)."""
    return [
        [g % slots, g, name, g - first]
        for first, last, name in files
        for g in range(max(first, counter - slots), last + 1)
    ]


class WindowStateCheckpointer:
    """Persist/restore the device window state across restarts.

    The offsets file above only replays the LAST batch; TIMEWINDOW state
    holds up to window+watermark of history that a restart would
    otherwise silently zero. The reference keeps that state in the Spark
    StreamingContext checkpoint (datax-host host/StreamingHost.scala:83-89
    ``StreamingContext.getOrCreate(checkpointDir, ...)``); here it is
    plain arrays.

    ``window.npz`` is the head, written last with the same
    atomic-replace + ``.old`` backup semantics as offsets.txt: the slot
    counter, the time base, the dictionary, every raw-row ring whole
    (``ring/<table>/col/<name>`` + ``ring/<table>/valid``) and, for a
    view that keeps per-slot partial aggregates, its key directory, slot
    times, slot generations and, for every live slot, the name of the
    slot file that holds its row (``partial/<view>/...``). Those rows are
    written a slot at a time: one file a checkpoint under
    ``window-slots/`` with the live slots changed since the last head
    landed (a slot's generation is the counter of the last batch that
    changed it), never the whole state. A slot of a processing-time
    window is written by one batch, so it is in one file; a slot of an
    event-time window changes with every batch that brings rows of its
    interval, late ones included, so a later checkpoint writes it again
    and the head names the newest file that holds it. A file is written
    once, under a name of its own, and is deleted only when neither the
    head nor ``.old`` names it for any slot: whichever of the two a
    restart reads finds every file it names, so a kill at any point
    restores a state some completed checkpoint described, late rows
    included.
    """

    FILE = "window.npz"
    BACKUP = "window.npz.old"
    SLOTS_DIR = "window-slots"

    def __init__(self, checkpoint_dir: str):
        self.dir = checkpoint_dir
        os.makedirs(checkpoint_dir, exist_ok=True)
        # slot counter of the head this object last wrote or loaded:
        # what ``FlowProcessor.snapshot_window_state(since=...)`` may
        # leave out. None: nothing on disk this process may build on
        self.landed_counter: Optional[int] = None
        # view -> slot -> (generation, file name, row in the file) as the
        # landed head names them, and as the head now in ``.old`` does
        self._slot_files: Dict[str, Dict[int, Tuple[int, str, int]]] = {}
        self._old_slot_files: Dict[str, Dict[int, Tuple[int, str, int]]] = {}
        self.last_bytes = 0  # bytes the last save wrote (head + slots)
        self.last_slots = 0  # slot rows the last save wrote

    @property
    def path(self) -> str:
        return os.path.join(self.dir, self.FILE)

    @property
    def backup_path(self) -> str:
        return os.path.join(self.dir, self.BACKUP)

    @property
    def slots_dir(self) -> str:
        return os.path.join(self.dir, self.SLOTS_DIR)

    def forget(self) -> None:
        """The processor did not take what ``load`` returned: the next
        snapshot is whole and builds on no slot file."""
        self.landed_counter = None
        self._slot_files = {}

    def save(self, snap: Dict) -> None:
        """snap: FlowProcessor.snapshot_window_state() output."""
        with _trace_span("checkpoint/window"):
            self._save(snap)

    def _save(self, snap: Dict) -> None:
        import json as _json

        import numpy as np

        arrays = snapshot_arrays(snap)
        counter = int(snap.get("slot_counter", 0))
        written = slots = 0
        files: Dict[str, Dict[int, Tuple[int, str, int]]] = {}
        if snap.get("partials"):
            with _trace_span("checkpoint/window-slots"):
                for view, part in snap["partials"].items():
                    files[view], n = self._save_slots(view, part, counter)
                    written += n
                    slots += len(part["rows"])
            for view, part in snap["partials"].items():
                arrays[f"partial/{view}/files_json"] = np.frombuffer(
                    _json.dumps({
                        "slots": int(part["slots"]),
                        "groups": int(part["groups"]),
                        "dtypes": {n: str(a.dtype)
                                   for n, a in part["parts"].items()},
                        "files": [[s, *e] for s, e in files[view].items()],
                    }).encode("utf-8"), dtype=np.uint8,
                )
        had_head = os.path.exists(self.path)
        if had_head:
            shutil.copyfile(self.path, self.backup_path)
        tmp = self.path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        written += os.path.getsize(tmp)
        _durable_replace(tmp, self.path)
        self._old_slot_files = self._slot_files if had_head else {}
        self._slot_files = files
        self.landed_counter = counter
        self.last_bytes = written
        self.last_slots = slots
        self._drop_unnamed_slot_files()

    def _save_slots(
        self, view: str, part: Dict, counter: int
    ) -> Tuple[Dict[int, Tuple[int, str, int]], int]:
        """Write the rows a snapshot brought (``part["rows"]``: the live
        slots changed since generation ``first_gen``) as one new file;
        returns, for every live slot, the file the new head names for it
        (the new one, or the one the landed head names when the slot has
        not changed since) and the bytes written."""
        import numpy as np

        gens, brought = part["slot_gen"], [int(s) for s in part["rows"]]
        landed = self._slot_files.get(view, {})
        named: Dict[int, Tuple[int, str, int]] = {}
        for s in set(np.flatnonzero(part["slot_live"])) - set(brought):
            entry = landed.get(int(s))
            if entry is None or entry[0] != gens[s]:
                raise ValueError(
                    f"window checkpoint of {view}: slot {s} (generation "
                    f"{gens[s]}) is not in the snapshot, which brings the "
                    f"slots changed since {part['first_gen']}, and the "
                    f"landed head names {entry} for it"
                )
            named[int(s)] = entry
        if not len(brought):
            return named, 0
        os.makedirs(self.slots_dir, exist_ok=True)
        name = (f"{view.replace(os.sep, '_')}.{int(part['first_gen'])}-"
                f"{counter - 1}.{time.time_ns():x}.npz")
        path = os.path.join(self.slots_dir, name)
        with open(path + ".tmp", "wb") as f:
            np.savez(f, **part["parts"])
            f.flush()
            os.fsync(f.fileno())
        size = os.path.getsize(path + ".tmp")
        _durable_replace(path + ".tmp", path)
        for row, s in enumerate(brought):
            named[s] = (int(gens[s]), name, row)
        return named, size

    def _drop_unnamed_slot_files(self) -> None:
        """Delete the slot files (and torn temp files) that neither the
        landed head nor the one in ``.old`` names."""
        if not os.path.isdir(self.slots_dir):
            return
        keep = {
            name
            for files in (self._slot_files, self._old_slot_files)
            for entries in files.values()
            for _gen, name, _row in entries.values()
        }
        for name in os.listdir(self.slots_dir):
            if name not in keep:
                try:
                    os.remove(os.path.join(self.slots_dir, name))
                except OSError:
                    pass

    def load(self) -> Optional[Dict]:
        """Restore a snapshot dict, falling back to the backup; None when
        no (readable) snapshot exists — including when a crash left only
        a torn ``window.npz.tmp`` behind (the tmp is never read; the
        previous complete checkpoint wins). A head whose slot files are
        missing or torn is as unreadable as a torn head. Partial
        aggregates come back whole (``parts``: [slots, groups])."""
        import numpy as np

        for path in (self.path, self.backup_path):
            if not os.path.exists(path):
                continue
            try:
                with np.load(path) as z:
                    snap = arrays_to_snapshot(z)
                    files = self._load_slots(z, snap)
            except Exception:
                continue
            self.landed_counter = int(snap["slot_counter"])
            self._slot_files = files
            return snap
        return None

    def _load_slots(self, z, snap: Dict) -> Dict[str, Dict]:
        """Fill every view's ``parts`` from the slot files its head
        names; returns those names. Raises when one cannot be read. A
        head written before slots had generations of their own (PR 32,
        33: processing-time windows only) is read too."""
        import json as _json

        import numpy as np

        files: Dict[str, Dict[int, Tuple[int, str, int]]] = {}
        for view, part in snap.get("partials", {}).items():
            head = _json.loads(
                z[f"partial/{view}/files_json"].tobytes().decode("utf-8")
            )
            k, d = head["slots"], head["groups"]
            entries = head["files"]
            if entries and len(entries[0]) == 3:
                entries = _slot_entries(
                    entries, k, int(snap["slot_counter"]))
            files[view] = {
                int(s): (int(gen), name, int(row))
                for s, gen, name, row in entries
            }
            if "slot_gen" not in part:
                part["slot_gen"] = np.full(k, -1, np.int32)
                for s, (gen, _name, _row) in files[view].items():
                    part["slot_gen"][s] = gen
            # a slot no file holds is not live: the batch (the interval)
            # that takes it over starts its row from the identities
            parts = {
                n: np.full((k, d), 0, np.dtype(dt))
                for n, dt in head["dtypes"].items()
            }
            by_file: Dict[str, List[Tuple[int, int]]] = {}
            for s, (_gen, name, row) in files[view].items():
                by_file.setdefault(name, []).append((s, row))
            for name, held in by_file.items():
                with np.load(os.path.join(self.slots_dir, name)) as zs:
                    for n in parts:
                        rows = zs[n]
                        for s, row in held:
                            parts[n][s] = rows[row]
            if set(np.flatnonzero(part["slot_live"])) - set(files[view]):
                raise ValueError(f"{view}: live slots named by no file")
            part["parts"] = parts
        return files
