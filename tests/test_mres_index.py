"""MRES's name index: ``entry``, ``column`` and ``update_metrics`` read
one row through a name -> row map, never a scan of the catalog."""
import pytest

from repro.core.mres import MRES
from repro.core.preferences import METRICS
from tests.conftest import make_entry


def _catalog(how):
    """Five entries, the first four added by ``how``, then one more
    by ``register``; returns the store and the entries in order."""
    entries = [make_entry(f"m{i}", accuracy=0.2 + 0.1 * i)
               for i in (3, 0, 4, 1)]
    m = MRES()
    if how == "register":
        for e in entries:
            m.register(e)
    else:
        m.register_many(entries)
    late = make_entry("late", accuracy=0.9)
    m.register(late)
    return m, entries + [late]


class _NoScan(list):
    """The store's entry list, refusing to be walked: a lookup that
    iterates it scans the catalog."""

    def __iter__(self):
        raise AssertionError("lookup by name scanned the entries")

    def index(self, *a, **k):
        raise AssertionError("lookup by name scanned the entries")


@pytest.mark.parametrize("how", ["register", "register_many"])
def test_entry_returns_the_registered_object(how):
    m, entries = _catalog(how)
    for e in entries:
        assert m.entry(e.name) is e
    # the caller may attach state to the returned entry (a runner)
    m.entry("m4").runner = "runner"
    assert entries[2].runner == "runner"


@pytest.mark.parametrize("lookup", ["entry", "column", "update_metrics"])
def test_unknown_name_raises_keyerror(lookup):
    m, _ = _catalog("register_many")
    call = getattr(m, lookup)
    with pytest.raises(KeyError, match="nope"):
        call("nope")


@pytest.mark.parametrize("bad", ["dup_existing", "dup_in_batch", "invalid"])
def test_failed_register_many_leaves_index_unchanged(bad):
    m, entries = _catalog("register_many")
    before = [(e.name, m.column(e.name)) for e in entries]
    batch = [make_entry("x0"), make_entry("x1")]
    if bad == "dup_existing":
        batch.append(make_entry("m0"))
    elif bad == "dup_in_batch":
        batch.append(make_entry("x0"))
    else:
        batch.append(make_entry("x2", task_types=("no-such-type",)))
    with pytest.raises((ValueError, AssertionError)):
        m.register_many(batch)
    assert len(m) == len(entries)
    assert [(e.name, m.column(e.name)) for e in entries] == before
    for e in entries:
        assert m.entry(e.name) is e
    for name in ("x0", "x1", "x2"):
        with pytest.raises(KeyError):
            m.entry(name)
    # the failed batch's names are free to register afterwards
    m.register_many([make_entry("x0"), make_entry("x1")])
    assert m.column("x0") == len(entries)
    assert m.column("x1") == len(entries) + 1


def test_update_metrics_reaches_the_indexed_entry():
    m, entries = _catalog("register_many")
    untouched = {e.name: dict(e.raw_metrics) for e in entries}
    m.update_metrics("m4", accuracy=0.01, latency_ms=7.0)
    assert entries[2].raw_metrics["accuracy"] == 0.01
    assert entries[2].raw_metrics["latency_ms"] == 7.0
    for e in entries:
        if e.name != "m4":
            assert e.raw_metrics == untouched[e.name]
    # the update dirtied the caches: m4 is now the least accurate
    acc = m.embeddings()[:, METRICS.index("accuracy")]
    assert int(acc.argmin()) == m.column("m4") == 2
    assert (acc > 0).sum() == len(entries) - 1


@pytest.mark.parametrize("how", ["register", "register_many"])
def test_column_matches_snapshot_name_order(how):
    m, entries = _catalog(how)
    names = m.snapshot()[1]
    assert [m.column(n) for n in names] == list(range(len(names)))
    m.register(make_entry("later"))
    m.register_many([make_entry("last0"), make_entry("last1")])
    names = m.snapshot()[1]
    assert names[-3:] == ["later", "last0", "last1"]
    for j, n in enumerate(names):
        assert m.column(n) == j
        assert m.entry(n).name == n


def test_lookup_by_name_does_not_scan_entries():
    m, entries = _catalog("register_many")
    m._entries = _NoScan(m._entries)
    for j, e in enumerate(entries):
        assert m.entry(e.name) is e
        assert m.column(e.name) == j
    m.update_metrics("m1", accuracy=0.5)
    assert entries[3].raw_metrics["accuracy"] == 0.5


def test_index_consistent_under_concurrent_registration():
    """Writers append (one at a time and in batches) while readers
    resolve names already registered: every name a reader sees maps
    to its own entry and to its row in a later snapshot."""
    import sys
    import threading

    m = MRES()
    m.register_many([make_entry(f"base{i}") for i in range(32)])
    errors = []
    stop = threading.Event()

    def writer(w):
        try:
            for k in range(40):
                if k % 2:
                    m.register(make_entry(f"w{w}-{k}"))
                else:
                    m.register_many([make_entry(f"w{w}-{k}-{j}")
                                     for j in range(3)])
        except Exception as e:                      # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                names = m.snapshot()[1]
                for j in range(0, len(names), 7):
                    n = names[j]
                    assert m.column(n) == j
                    assert m.entry(n).name == n
        except Exception as e:                      # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        writers = [threading.Thread(target=writer, args=(w,))
                   for w in range(4)]
        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in readers + writers)
    assert not errors, errors[:3]
    names = m.snapshot()[1]
    assert len(names) == len(m) == 32 + 4 * (20 + 20 * 3)
    assert [m.column(n) for n in names] == list(range(len(names)))
