"""Shared fixtures + the tier-marker gate. NOTE: no XLA_FLAGS here — smoke
tests and benches must see the real single CPU device; only
launch/dryrun.py fakes 512 devices."""
import pytest

from repro.launch.mesh import make_mesh

#: every collected test must carry at least one of these (pytest.ini
#: declares them; --strict-markers rejects typos). tier1 = fast,
#: in-process; tier2 = slow 8-device subprocess equivalence tests.
#: ``make test-tier1`` runs ``-m "tier1 and not tier2"``.
TIER_MARKERS = ("tier1", "tier2")


def pytest_collection_modifyitems(config, items):
    missing = [item.nodeid for item in items
               if not any(item.get_closest_marker(m) for m in TIER_MARKERS)]
    if missing:
        head = "\n  ".join(missing[:10])
        raise pytest.UsageError(
            f"{len(missing)} collected test(s) lack a tier marker "
            f"({'/'.join(TIER_MARKERS)}) — add a module-level pytestmark "
            f"or a @pytest.mark.tierN decorator:\n  {head}")
    # Within each file the tier2 tests run first. Under ``--dist
    # loadfile`` a worker takes its next file once two tests of its
    # current one are left; a tier2 test left for last would then wait
    # behind that next file.
    first = {}
    for i, item in enumerate(items):
        first.setdefault(item.path, i)
    items.sort(key=lambda item: (first[item.path],
                                 not item.get_closest_marker("tier2")))


@pytest.fixture(scope="session")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))
