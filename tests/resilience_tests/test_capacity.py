"""Capacity-transfer protocol (ISSUE 16): the CapacityBroker's
conversion state machine, both role floors, the hysteresis/cooldown
rails, and — the headline — the conversion-journal crash-recovery
matrix: a seeded kill at EVERY state-machine step leaves an orphaned
journal key that survivors detect, type, and roll forward or abort
with no zombie presence in either role group.  Tier-1."""

import threading

import numpy as np
import pytest

from chainermn_tpu import observability
from chainermn_tpu.communicators._membership import ElasticMembership
from chainermn_tpu.communicators.fault_schedule import (FaultSchedule,
                                                        RankPreempted)
from chainermn_tpu.elastic import (CONVERSION_STEPS, CapacityBroker,
                                   CapacityFloorError,
                                   CapacityProtocolError, LocalTrainGroup)
from chainermn_tpu.serving.fleet import ReplicaFleet

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _fresh_registry():
    observability.reset_registry()
    yield
    observability.reset_registry()


# -- fakes --------------------------------------------------------------------

class KV:
    """Thread-safe in-memory stand-in for the coordination KV store
    (the real client's narrow surface: try_get raises on missing)."""

    def __init__(self):
        self.store = {}
        self.lock = threading.Lock()

    def key_value_set(self, k, v):
        with self.lock:
            self.store[k] = str(v)

    def key_value_try_get(self, k):
        with self.lock:
            if k not in self.store:
                raise KeyError(k)
            return self.store[k]

    def key_value_delete(self, k):
        with self.lock:
            self.store.pop(k, None)


def _member(kv, rank, role="elastic", world=2, **kw):
    kw.setdefault("settle_s", 0.05)
    kw.setdefault("poll_s", 0.002)
    kw.setdefault("timeout_ms", 4000)
    return ElasticMembership(kv, rank=rank, world=world, role=role, **kw)


class _Scheduler:
    def __init__(self):
        self.q = []

    def pending(self, tenant=None):
        return len(self.q)

    def tenant_depths(self):
        out = {}
        for r in self.q:
            out[r.tenant] = out.get(r.tenant, 0) + 1
        return out

    def requeue_front(self, request, preempted=True):
        self.q.insert(0, request)

    def next_admission(self, arrived_by=None):
        return self.q.pop(0) if self.q else None


class _Allocator:
    num_pages = 8

    def pages_for(self, total):
        return 1

    def free(self, request_id):
        pass


class FakeEngine:
    """The LocalReplica surface without a jit in sight — state is a
    tiny pytree so the fleet's serialize/adopt weight path (and its
    bit-identity) still runs for real."""

    def __init__(self, seed=0):
        rng = np.random.RandomState(seed)
        self.state = {"w": rng.rand(4).astype(np.float32)}
        self.decode_steps = 0
        self.running = []
        self.completed = []
        self.max_context = 64
        self.scheduler = _Scheduler()
        self.allocator = _Allocator()

    def submit(self, request):
        self.scheduler.q.append(request)

    def step(self, now=None):
        self.decode_steps += 1
        return {"admitted": 0, "decoded": 0, "running": 0, "evicted": 0,
                "occupancy": 0.0, "capacity_x": 1.0}

    def drop_in_flight(self):
        pass


def _weights(fleet, rid):
    return np.asarray(fleet.replicas[rid].engine.state["w"])


def _world(world=3, schedule=None, min_world=1, **kw):
    """One broker over a 3-rank training group and a 1-replica fleet,
    on a synthetic clock (`t[0]`, advanced by hand)."""
    t = [0.0]
    train = LocalTrainGroup(world=world)
    fleet = ReplicaFleet(engine_factory=lambda rid: FakeEngine(seed=0),
                         replicas=1, clock=lambda: t[0])
    broker = CapacityBroker(
        train, fleet, engine_factory=lambda r: FakeEngine(seed=100 + r),
        min_world=min_world, stale_s=0.5, schedule=schedule,
        clock=lambda: t[0], **kw)
    return train, fleet, broker, t


# -- journal over the real membership protocol --------------------------------

def test_journal_round_trip_and_role_shared_visibility():
    """The conversion journal lives OUTSIDE both role groups' key
    prefixes: a training-role member and a fleet-role member sharing
    one KV store read the same entries."""
    kv = KV()
    train = _member(kv, 0, role="elastic")
    fleet = _member(kv, 0, role="fleet")
    assert train.read_conversion(1) is None
    train.journal_conversion("LEAVE_ANNOUNCED", note="queue pressure",
                             rank=1)
    assert train.read_conversion(1) == ("LEAVE_ANNOUNCED", 1,
                                        "queue pressure")
    # the fleet-role member sees the SAME journal
    assert fleet.read_conversion(1) == ("LEAVE_ANNOUNCED", 1,
                                        "queue pressure")
    # the beat advances on every write (the liveness signal)
    train.journal_conversion("CONVERTING", rank=1)
    assert fleet.read_conversion(1) == ("CONVERTING", 2, "")
    assert fleet.scan_conversions() == {1: ("CONVERTING", 2, "")}
    # but role-group keys stay disjoint: no view/intent bleed
    train.announce_leave(note="x")
    assert fleet.scan_conversions() == {1: ("CONVERTING", 2, "")}
    fleet.clear_conversion(1)
    assert train.read_conversion(1) is None
    assert train.scan_conversions() == {}


def test_retract_join_scrubs_intent_without_leave():
    kv = KV()
    m0, m1 = _member(kv, 0), _member(kv, 1)
    m1.announce_join(note="wants in")
    assert m0.pending_joins() == ()   # already in the bootstrap view
    m1.announce_leave(note="gone")
    v = m0.resolve(expect={0})
    assert v.members == (0,)
    m1.announce_join(note="back")
    assert m0.pending_joins(v) == (1,)
    # a survivor scrubs the DEAD rank's intent: no admission ever
    m0.retract_join(rank=1)
    assert m0.pending_joins(v) == ()


# -- the round trip -----------------------------------------------------------

def test_convert_retire_round_trip():
    """training → fleet → training: the donor leaves training, serves
    with the fleet root's weights BIT-IDENTICALLY (the multicast-tree
    sync), retires, and rejoins; the journal is scrubbed and the
    per-role gauges track both world sizes throughout."""
    train, fleet, broker, t = _world()
    reg = observability.registry()
    gauge = reg.gauge("chainermn_tpu_role_world_size")
    assert gauge.value(role="elastic") == 3
    assert gauge.value(role="fleet") == 1

    rank = broker.convert_to_serving(now=0.0)
    assert rank == 2                      # default donor: highest rank
    assert rank not in train.current_view()           # left training
    rid = broker.converted[rank]
    assert rid in {r.rid for r in fleet.live_replicas()}
    # adopted weights are byte-equal to the root's (tree sync)
    np.testing.assert_array_equal(_weights(fleet, rid),
                                  _weights(fleet, 0))
    # the journal parks at SERVING for the whole stint
    assert train.read_conversion(rank)[0] == "SERVING"
    assert gauge.value(role="elastic") == 2
    assert gauge.value(role="fleet") == 2

    back = broker.retire_to_training(now=1.0)
    assert back == rank
    assert rank in train.current_view()               # rejoined
    assert rid not in {r.rid for r in fleet.live_replicas()}
    assert train.read_conversion(rank) is None        # journal scrubbed
    assert broker.converted == {}
    assert gauge.value(role="elastic") == 3
    assert gauge.value(role="fleet") == 1
    assert broker.stats["conversions"] == 1
    assert broker.stats["retires"] == 1
    assert broker.stats["role_transfers"] == 2


def test_floors_refuse_typed_with_both_views():
    """Training never below min_world, the fleet never below one live
    replica — violations refuse with CapacityFloorError carrying BOTH
    role views."""
    train, fleet, broker, t = _world(world=2, min_world=2)
    with pytest.raises(CapacityFloorError) as ei:
        broker.convert_to_serving()
    assert ei.value.training_view is not None
    assert ei.value.training_view.members == (0, 1)
    assert ei.value.fleet_view is not None
    assert ei.value.fleet_view.role == "fleet"
    assert broker.stats["floor_refusals"] == 1

    # fleet floor: retire the only live replica → refused
    train2, fleet2, broker2, _ = _world(world=3, min_world=1)
    rank = broker2.convert_to_serving(now=0.0)
    fleet2.preempt(0)       # the original replica dies: converted rank
    #                         is now the fleet's LAST live replica
    with pytest.raises(CapacityFloorError) as ei:
        broker2.retire_to_training(rank, now=1.0)
    assert ei.value.fleet_view is not None
    # the refusal moved nothing: the rank is still serving, the
    # journal still parked at SERVING
    assert rank not in train2.current_view()
    assert train2.read_conversion(rank)[0] == "SERVING"
    assert broker2.converted[rank] in {r.rid
                                       for r in fleet2.live_replicas()}


def test_state_machine_rejects_illegal_transitions():
    train, fleet, broker, t = _world()
    with pytest.raises(CapacityProtocolError):
        broker._journal(2, "CONVERTING")       # skips LEAVE_ANNOUNCED
    broker._journal(2, "LEAVE_ANNOUNCED")
    with pytest.raises(CapacityProtocolError):
        broker._journal(2, "SERVING")          # skips CONVERTING
    with pytest.raises(CapacityProtocolError):
        broker._journal(2, "LEAVE_ANNOUNCED")  # rewind
    broker._journal(2, "CONVERTING")
    broker._journal(2, "SERVING")
    broker._journal(2, "RETIRING")
    broker._journal(2, "REJOINING")
    train.clear_conversion(2)


# -- auto-apply + hysteresis --------------------------------------------------

def test_apply_executes_decisions_with_cooldowns():
    train, fleet, broker, t = _world(convert_cooldown_s=5.0,
                                     retire_cooldown_s=5.0)
    assert broker.apply(0, now=0.0) is None
    assert broker.apply(1, now=0.0) == ("convert", 2)
    # cooldown: a second +1 inside the window moves nothing
    assert broker.apply(1, now=2.0) is None
    assert broker.apply(1, now=6.0) == ("convert", 1)
    # training floor (min_world=1): a third +1 refuses quietly
    assert broker.apply(1, now=20.0) is None
    assert broker.stats["floor_refusals"] == 1
    # drain: retires come back LIFO, with their own cooldown
    assert broker.apply(-1, now=20.0) == ("retire", 1)
    assert broker.apply(-1, now=21.0) is None
    assert broker.apply(-1, now=30.0) == ("retire", 2)
    # nothing of ours left: -1 with no converted rank moves nothing
    assert broker.apply(-1, now=40.0) is None
    assert train.current_view().members == (0, 1, 2)


def test_apply_false_preserves_surfaced_only_behavior():
    """PR 15's contract under auto_apply=False: decisions are counted,
    nothing moves."""
    train, fleet, broker, t = _world(auto_apply=False)
    assert broker.apply(1, now=0.0) is None
    assert broker.apply(-1, now=1.0) is None
    assert broker.stats["surfaced"] == 2
    assert broker.stats["role_transfers"] == 0
    assert train.current_view().members == (0, 1, 2)
    assert len(fleet.live_replicas()) == 1


# -- the crash-recovery matrix ------------------------------------------------

# step -> (leg, expected orphan action)
_MATRIX = [("LEAVE_ANNOUNCED", "convert", "abort"),
           ("CONVERTING", "convert", "abort"),
           ("SERVING", "convert", "roll-forward"),
           ("RETIRING", "retire", "roll-forward"),
           ("REJOINING", "retire", "abort")]


def _assert_no_zombie(train, fleet, rank, broker):
    """The matrix's invariant: after recovery the dead rank is present
    in NEITHER role group and its journal key is gone."""
    assert rank not in train.current_view().members
    assert rank not in {r.rid for r in fleet.live_replicas()}
    rid = broker.converted.get(rank, rank)
    assert rid not in {r.rid for r in fleet.live_replicas()}
    assert train.read_conversion(rank) is None
    assert rank not in broker.converted


@pytest.mark.parametrize("step,leg,expect", _MATRIX,
                         ids=[s for s, _, _ in _MATRIX])
def test_seeded_kill_at_every_step_recovers(step, leg, expect):
    """A seeded preempt lands exactly at ``step`` (FaultSchedule step
    targeting); the orphaned journal key is detected after stale_s,
    typed, and rolled forward or aborted — no zombie presence in
    either role group, no capacity conjured or leaked."""
    schedule = FaultSchedule([dict(op="capacity.convert",
                                   action="preempt", prob=1.0,
                                   step=step, rank=2)],
                             seed=7).bind_rank(2)
    train, fleet, broker, t = _world(schedule=schedule)

    if leg == "convert":
        with pytest.raises(RankPreempted):
            broker.convert_to_serving(now=0.0)
        killed_rank = 2
    else:
        broker.schedule = None           # the convert leg runs clean
        killed_rank = broker.convert_to_serving(now=0.0)
        broker.schedule = schedule
        with pytest.raises(RankPreempted):
            broker.retire_to_training(killed_rank, now=0.0)

    # the journal records exactly the step the kill landed at
    entry = train.read_conversion(killed_rank)
    assert entry is not None and entry[0] == step

    # a kill at SERVING means the replica itself died too — the
    # fleet's own typed detection sheds it (here: simulated preempt),
    # and the journal roll-forward must not resurrect it
    if step == "SERVING":
        rid = broker.converted.get(killed_rank, killed_rank)
        fleet.preempt(rid, now=0.0)

    # survivor sweep: first sight arms the staleness clock, nothing
    # happens before stale_s
    assert broker.recover_orphans(now=1.0) == ()
    assert train.read_conversion(killed_rank) is not None
    # past stale_s with a frozen beat: the orphan is typed and resolved
    actions = broker.recover_orphans(now=2.0)
    assert actions == ((killed_rank, step, expect),)
    _assert_no_zombie(train, fleet, killed_rank, broker)
    key = "aborted" if expect == "abort" else "rolled_forward"
    assert broker.stats[key] == 1
    # the fleet's original replica survived every scenario (no
    # capacity leaked past the floor)
    assert 0 in {r.rid for r in fleet.live_replicas()}


def test_orphan_sweep_skips_live_conversions():
    """A beat that ADVANCES between sweeps is a live conversion; a
    healthy SERVING stint (rank live in the fleet) is never treated as
    orphaned no matter how stale its parked journal entry is."""
    train, fleet, broker, t = _world()
    rank = broker.convert_to_serving(now=0.0)
    # parked at SERVING, live in the fleet: sweeps never touch it
    assert broker.recover_orphans(now=0.0) == ()
    assert broker.recover_orphans(now=100.0) == ()
    assert train.read_conversion(rank)[0] == "SERVING"
    # an advancing beat re-arms the staleness clock
    train.journal_conversion("RETIRING", rank=rank)   # retire starts…
    assert broker.recover_orphans(now=100.0) == ()    # first sight
    train.journal_conversion("RETIRING", rank=rank,
                             note="still moving")     # beat advances
    assert broker.recover_orphans(now=200.0) == ()    # re-armed
    # only a FROZEN beat past stale_s is an orphan
    assert broker.recover_orphans(now=200.2) == ()
    actions = broker.recover_orphans(now=300.0)
    assert actions == ((rank, "RETIRING", "roll-forward"),)
    _assert_no_zombie(train, fleet, rank, broker)


def test_half_admitted_carcass_is_discarded():
    """A kill between the fleet resolve and the weight sync leaves a
    live=False carcass in the replica map; the CONVERTING abort evicts
    it through the fleet's typed discard (a LIVE replica refuses)."""
    train, fleet, broker, t = _world()
    # simulate the half-join by hand: journal to CONVERTING, then
    # plant a never-went-live replica like a mid-join crash would
    broker._journal(2, "LEAVE_ANNOUNCED")
    train.announce_leave(rank=2)
    broker._journal(2, "CONVERTING")
    from chainermn_tpu.serving.fleet import LocalReplica
    carcass = LocalReplica(2, FakeEngine(seed=9))
    carcass.live = False
    fleet.replicas[2] = carcass
    with pytest.raises(ValueError):
        fleet.discard(0)                 # live replicas refuse discard
    assert broker.recover_orphans(now=0.0) == ()
    actions = broker.recover_orphans(now=1.0)
    assert actions == ((2, "CONVERTING", "abort"),)
    assert 2 not in fleet.replicas
    _assert_no_zombie(train, fleet, 2, broker)


def test_converting_orphan_with_landed_join_rolls_forward():
    """The completes-or-aborts dichotomy's completing half: a kill
    AFTER the join landed but before the SERVING journal write rolls
    the record forward — the replica keeps serving."""
    train, fleet, broker, t = _world()
    rank = broker.convert_to_serving(now=0.0)
    rid = broker.converted[rank]
    # rewind the journal to CONVERTING, as if the SERVING write was
    # the casualty
    train._journal[rank] = ("CONVERTING", 2, "")
    broker.converted.pop(rank)
    assert broker.recover_orphans(now=10.0) == ()
    actions = broker.recover_orphans(now=11.0)
    assert actions == ((rank, "CONVERTING", "roll-forward"),)
    # rolled FORWARD: the journal now says SERVING and the replica is
    # still live — no capacity was thrown away
    assert train.read_conversion(rank)[0] == "SERVING"
    assert rid in {r.rid for r in fleet.live_replicas()}
    assert broker.converted[rank] == rid


def test_conversion_steps_constant_is_ordered():
    assert CONVERSION_STEPS == ("LEAVE_ANNOUNCED", "CONVERTING",
                                "SERVING", "RETIRING", "REJOINING")
