"""Physical KV-cache block accounting for the paged decode engine.

The paged cache (ops/kv_cache_ops.py paged variants) is a pool of
fixed-size physical blocks addressed through runtime-fed per-slot block
tables. Two host-side structures own the pool:

- ``BlockAllocator``: free-list + per-block refcounts over blocks
  ``1..num_blocks-1`` (block 0 is the TRASH block — table filler and
  pad-write target — and is never handed out). Admission becomes a
  blocks-available decision; a finished or evicted request's ``deref``
  returns refcount-0 blocks to the free list.
- ``PrefixCache``: content-addressed map from prompt-prefix CHAIN hashes
  (one per full block of prompt tokens) to the physical block already
  holding that prefix's K/V. A hit maps the new request's leading table
  entries onto the SAME physical blocks (refcount++) — the identical
  system prompt of a million-user service is stored once and its
  prefill computed once. The cache itself holds one reference per
  registered block, so prefix blocks survive their creator request and
  are reclaimed lazily, LRU-deepest-first, only under allocation
  pressure.

Speculative decoding (PR 13) rides the same accounting: the verify
window's tail blocks are ordinary refcount-1 allocations, and ROLLBACK
after a rejected draft is nothing but ``deref_many`` on the blocks past
the accepted write head — the block table is the rollback mechanism, so
a rejected speculation costs exactly the allocator bookkeeping of the
blocks it briefly held. The draft model keeps a SECOND allocator over
its own pool (sized ``slots * max_len / block_size`` + trash, so
per-slot growth can never starve) with no prefix cache — draft K/V are
model-specific throwaways.

Pools that are not the allocator's have a bookkeeper a kind of index
(`slot_bookkeeper`; models/transformer.py `cache_pools` says which pool has
which, how a shared prefix is resumed over them and why speculation is
refused). A
model with WINDOW layers (a query sees the last ``sliding_window`` keys
only) keeps those layers' K and V in pools where ``WindowRings`` gives every
slot ``ring`` blocks for as long as it is resident, used as a ring — logical
block ``b`` lies in column ``b % ring`` of the slot's window table — so a
page behind the window is handed on by being written over, a slot's share
never grows with its context and no step asks an allocator for anything.
STATE-SPACE layers keep a row a slot (``SlotRows``), and beside the slots'
rows SNAPSHOT rows: the state at a block's edge, held by the prefix cache's
entry of the block that ends there, which a later reader of the same prefix
resumes from.

Sharing is at FULL-BLOCK granularity. Because a block's K/V rows depend
only on tokens at or before them (causal), a block fully covered by
prompt tokens is immutable once prefilled — the one exception is a
request whose ENTIRE prompt lands on shared blocks (prompt length a
multiple of block_size and all blocks hit): its last prompt position
must be recomputed to produce the first token, which makes its final
block's row a divergent write → the engine copies that block first
(copy-on-write, ``kv_block_cow_total``) and writes into the private
copy. Neither sharer ever observes the other's tokens.
"""
import hashlib
import threading

from .. import monitor

__all__ = ['BlockAllocator', 'PrefixCache', 'QuotaBlockAllocator',
           'SlotRows', 'WindowRings', 'chain_hashes', 'slot_bookkeeper']


def chain_hashes(tokens, block_size):
    """One chained content hash per FULL block of `tokens`: hash i
    commits to every token in blocks 0..i, so equal hash means equal
    whole prefix (not just an equal i-th block)."""
    out, h = [], b'kv-prefix'
    n_full = len(tokens) // block_size
    for i in range(n_full):
        blk = tokens[i * block_size:(i + 1) * block_size]
        h = hashlib.sha1(
            h + b'|' + b','.join(b'%d' % int(t) for t in blk)).digest()
        out.append(h)
    return out


class BlockAllocator(object):
    """Free-list + refcount accounting over `num_blocks` physical blocks.
    Block 0 is reserved (trash) and never allocated; `capacity` is the
    usable pool size (num_blocks - 1).

    Thread-safe: a fleet hands per-tenant `QuotaBlockAllocator` views
    over ONE pool to multiple decode-loop threads, so every mutation
    (and every check that gates one) runs under the pool's reentrant
    `lock` — views take the SAME lock so their quota check-and-charge
    is atomic against concurrent tenants."""

    def __init__(self, num_blocks, block_size):
        if num_blocks < 2:
            raise ValueError(
                "paged cache needs >= 2 physical blocks (block 0 is the "
                "reserved trash block), got %d" % num_blocks)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.lock = threading.RLock()
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = [0] * self.num_blocks

    @property
    def capacity(self):
        return self.num_blocks - 1

    def available(self):
        with self.lock:
            return len(self._free)

    def in_use(self):
        with self.lock:
            return self.capacity - len(self._free)

    def refcount(self, bid):
        with self.lock:
            return self._ref[bid]

    def alloc(self, n):
        """n fresh blocks at refcount 1, or None when the free list is
        short (nothing is partially allocated on failure)."""
        with self.lock:
            if n > len(self._free):
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
            return out

    def ref(self, bid):
        with self.lock:
            if self._ref[bid] < 1:
                raise ValueError("ref of unallocated block %d" % bid)
            self._ref[bid] += 1

    def deref(self, bid):
        """Drop one reference; a refcount-0 block returns to the free
        list. Returns True when the block was actually freed."""
        with self.lock:
            if self._ref[bid] < 1:
                raise ValueError("deref of unallocated block %d" % bid)
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                self._free.append(bid)
                return True
            return False

    def deref_many(self, bids):
        """`deref` a batch (slot release, speculative-tail rollback);
        returns how many blocks actually went back to the free list."""
        with self.lock:
            freed = 0
            for b in bids:
                if self.deref(b):
                    freed += 1
            return freed


class WindowRings(object):
    """The window layers' pools: a slot's table has `ring` columns, logical
    block ``b`` of its tenant in column ``b % ring``, and the blocks come
    from an allocator of the pools' own (``blocks``; block 0 is the trash
    block, an idle slot's table row and an empty column).

    A tenant holds one reference to each block in its columns. When it
    OPENS logical block ``b`` (`advance`), the column's old block lies a
    whole ring behind the oldest key still seen: where nobody else holds it
    the tenant writes over it where it lies, as a ring does; where the
    prefix cache or another tenant does (a shared prefix's block), the
    tenant lets go of it and a fresh block takes the column. A prefill
    chunk reads the ``reach`` rows before its first and writes its own in
    ONE program through ONE table, so a shared block that it still reads
    and whose column it opens is first copied into the fresh one (`moved`:
    the engine makes the copy ahead of the dispatch). A request that
    resumes at a shared prefix's edge (`resume`) gets the blocks of the
    ``reach`` rows before it in its columns, referenced, from the cache
    that kept them (`PrefixCache`'s ``side``).

    The pools have ``slots * ring`` blocks and what `cached` adds for the
    blocks the cache alone holds: the tenants never hold more than the
    first, the cache gives its own up under pressure (`evict`), so `advance`
    always finds a block."""

    # its feed ([rows, width]: a slot's ring, column by column), and the
    # series that what `advance` and `release` return is booked under
    feed, series = 'gen_wtab', 'kv_window_blocks_recycled_total'
    # ... the blocks that `resume` put into a tenant's columns, and the
    # rows of the blocks that `moved` had copied
    shared, copied = 'kv_window_blocks_shared_total', \
        'kv_window_rows_copied_total'
    resumed = None      # ... no series of the tokens a resume skipped
    # blocks a copy on the device takes at once (None: the widest bucket's
    # and one), and the scope it runs under
    batch, scope = None, None

    def __init__(self, slots, ring, block_size, reach=None, cached=0):
        self.ring = self.width = int(ring)
        self.block_size = int(block_size)
        # rows behind a position that a query there still reads
        self.reach = (self.ring - 2) * self.block_size if reach is None \
            else int(reach)
        self.blocks = BlockAllocator(int(slots) * self.ring + 1 + int(cached),
                                     block_size)
        self.cache = None       # the PrefixCache that holds blocks of ours
        self._opened = [0] * int(slots)   # logical blocks a tenant opened
        self._tables = [[0] * self.ring for _ in range(int(slots))]
        # the logical block each column holds (-1: none)
        self._holds = [[-1] * self.ring for _ in range(int(slots))]
        self._moved = []

    @property
    def capacity(self):
        return self.blocks.capacity

    def table(self, slot):
        """The slot's window table: its blocks, in column order."""
        return self._tables[slot]

    def held(self, slot, block):
        """The id of logical block `block` in the slot's ring, or None
        where the ring holds another by now."""
        col = block % self.ring
        return self._tables[slot][col] \
            if self._holds[slot][col] == block else None

    def _fresh(self):
        ids = self.blocks.alloc(1)
        if ids is None and self.cache is not None:
            # a ring's worth at once: the cache sorts its entries to find
            # the least recently used, once in `ring` blocks and not a block
            self.cache.evict_side_for(self.ring)
            ids = self.blocks.alloc(1)
        if ids is None:
            raise RuntimeError("the window layers' pool has no block left: "
                               "%d in use of %d" % (self.blocks.in_use(),
                                                    self.capacity))
        return ids[0]

    def advance(self, slot, length, start=None):
        """The slot's tenant is about to write positions ``start .. length
        - 1`` (a decode step: the last one alone). Opens the logical blocks
        up to the last of them; returns how many columns took a new block
        in place of one the window left behind."""
        bs, table, holds = self.block_size, self._tables[slot], \
            self._holds[slot]
        first = length - 1 if start is None else start
        recycled = 0
        for b in range(self._opened[slot], -(-length // bs)):
            col = b % self.ring
            old, was = table[col], holds[col]
            if old and self.blocks.refcount(old) > 1:
                # somebody else's too: it stays as it is
                table[col] = self._fresh()
                if (was + 1) * bs > first - self.reach:
                    self._moved.append((old, table[col]))
                self.blocks.deref(old)
            elif not old:
                table[col] = self._fresh()
            recycled += was >= 0
            holds[col] = b
        self._opened[slot] = max(self._opened[slot], -(-length // bs))
        return recycled

    def moved(self):
        """(from, to) block ids: shared blocks that `advance` took out of a
        column whose rows the dispatch at hand still reads, and the blocks
        that took the columns. The caller copies them before it dispatches."""
        moved, self._moved = self._moved, []
        return moved

    def snapshot(self, slot, block):
        """The dispatch that ended with logical block `block` is out: a
        ring's blocks are the cache's to hold as they lie (`held`), nothing
        is copied."""

    def resume(self, slot, depth, ids):
        """A new tenant that resumes at block `depth`: `ids` are the blocks
        of the logical blocks ``depth - len(ids) .. depth - 1``, referenced
        for it by the caller."""
        self._opened[slot] = depth
        for b, bid in enumerate(ids, depth - len(ids)):
            self._tables[slot][b % self.ring] = bid
            self._holds[slot][b % self.ring] = b

    def release(self, slot):
        """The tenant is gone: the blocks it held, handed back."""
        held = [b for b in self._tables[slot] if b]
        self.blocks.deref_many(held)
        self._opened[slot] = 0
        self._tables[slot] = [0] * self.ring
        self._holds[slot] = [-1] * self.ring
        return len(held)

    def in_use(self):
        """Blocks in the slots' columns, each once."""
        return len({b for table in self._tables for b in table if b})

    def report(self, stats):
        """Into an engine's `stats()`: the blocks in the resident slots'
        rings (at most slots x ring), and those the prefix cache alone
        holds; with the free ones they are the capacity."""
        in_use = self.in_use()
        stats['blocks']['window'] = {'capacity': self.capacity,
                                     'ring': self.ring, 'in_use': in_use}
        if self.cache is not None:
            cached = self.blocks.in_use() - in_use
            stats['blocks']['window']['cached'] = cached
            monitor.set_gauge('kv_window_blocks_cached', float(cached))


class SlotRows(object):
    """The state-space layers' pools, a row a slot, behind `WindowRings`'
    interface: slot `i` owns row ``i + 1`` (row 0 is the trash row, what a
    slot that sits a step out is fed) from its admission, a chunked one
    too, to its release -- so a row is in use while its slot is taken
    (`free`: the engine's own list of free slots), nothing is advanced and
    nothing handed back.

    SNAPSHOT ROWS (`snapshots` > 0: the engine shares prefixes). Behind the
    slots' rows the pools have spare ones, ids ``1 .. snapshots`` of an
    allocator of their own (`blocks`; id ``s`` is row ``slots + s``). Where
    a prefill dispatch ends on a block's edge the slot's row IS the state
    at that position, in every 'row' pool and layer, and `snapshot` takes a
    spare row for a copy of it; the prefix cache's entry of the block that
    ENDS at the edge holds the row as its `side` (`held` names it to
    `PrefixCache.register`), one reference, and gives it up under the rows'
    own pressure (least recently used first; an evicted row only shortens
    later hits). A request that matches the chain resumes at the deepest
    edge whose entry still holds a row (`PrefixCache.side_run` with a
    `reach` of one row): `resume` has that row copied into the new tenant's.
    The copies are (from, to) rows that `moved` hands to the engine, which
    makes them on the device in dispatch order -- behind the chunk that left
    the state, ahead of whatever overwrites either row."""

    feed, width, series = 'gen_srow', 1, None
    # a tenant's resumes from a snapshot row (`resume`), the rows that
    # `snapshot` wrote and that the rows' own pressure gave up; no series
    # for what `moved` copies (both ends book their own)
    shared, copied = 'state_snapshot_resumes_total', None
    resumed = 'state_snapshot_tokens_resumed_total'
    written, evicted = 'state_snapshot_rows_written_total', \
        'state_snapshot_evictions_total'
    # rows a copy on the device takes at once, and the scope it runs under
    batch, scope = 1, 'paddle_tpu:state_snapshot'

    def __init__(self, slots, free, snapshots=0, block_size=1, reach=1):
        self.capacity, self._free = int(slots), free
        self.reach = int(reach)
        self.blocks = BlockAllocator(int(snapshots) + 1, block_size) \
            if snapshots else None
        self.cache = None       # the PrefixCache whose entries hold the rows
        self._snap = {}         # slot -> (logical block, id) of its last one
        self._moved, self._lent = [], []

    def table(self, slot):
        return slot + 1

    def advance(self, slot, length=None, start=None):
        return 0

    def release(self, slot):
        self._snap.pop(slot, None)
        return 0

    def snapshot(self, slot, block):
        """The dispatch that ended with logical block `block` is out: a
        spare row takes the slot's row as it leaves it. Without a row to
        spare -- every one in the hands of an admission -- nothing is
        taken."""
        ids = self.blocks.alloc(1)
        if ids is None:
            monitor.inc(self.evicted, self.cache.evict_side_for(1))
            ids = self.blocks.alloc(1)
        if ids is None:
            return
        self._snap[slot] = (block, ids[0])
        self._moved.append((slot + 1, self.capacity + ids[0]))
        # the book's own reference, until the copy is handed on
        self._lent.append(ids[0])
        monitor.inc(self.written)

    def held(self, slot, block):
        """The id of the snapshot taken of the slot's row where logical
        block `block` ended, or None."""
        at, sid = self._snap.get(slot, (None, None))
        return sid if at == block else None

    def resume(self, slot, depth, ids):
        """A new tenant that resumes at block `depth`: `ids` is the snapshot
        row of that edge, referenced for it by the caller until its copy
        into the slot's row is handed on -- or nothing, a start from zeros."""
        self._snap.pop(slot, None)
        for sid in ids:
            self._moved.append((self.capacity + sid, slot + 1))
            self._lent.append(sid)

    def moved(self):
        """(from, to) rows to copy in every 'row' pool ahead of the next
        dispatch. What `snapshot` and `resume` held of a row for the copy's
        sake goes back: a program dispatched later cannot overtake it."""
        moved, self._moved = self._moved, []
        if self._lent:
            self.blocks.deref_many(self._lent)
            self._lent = []
        return moved

    def in_use(self):
        return self.capacity - len(self._free)

    def report(self, stats):
        stats['state'] = {'capacity': self.capacity, 'in_use': self.in_use()}
        if self.blocks is not None:
            stats['state']['snapshots'] = {
                'rows': self.blocks.capacity, 'in_use': self.blocks.in_use()}
            monitor.set_gauge('state_snapshot_rows_in_use',
                              float(self.blocks.in_use()))


def slot_bookkeeper(pool, width, slots, block_size, free):
    """The bookkeeper of the pools indexed as `pool` is (a row of models/
    transformer.py `cache_pools`; `width`: the columns of a slot's row of
    their feed): 'ring', a slot's ring of blocks, the trash block, and what
    is left for the prefix cache's own; 'row', a slot's row, the trash row,
    and what is left for the snapshot rows."""
    if pool.index == 'ring':
        return WindowRings(slots, width, block_size, pool.reach,
                           pool.shape[0] - 1 - slots * width)
    if pool.index == 'row':
        return SlotRows(slots, free, pool.shape[0] - 1 - slots, block_size,
                        pool.reach)
    raise ValueError("no bookkeeper for a pool indexed by %r" % (pool.index,))


class QuotaBlockAllocator(object):
    """A per-tenant VIEW over a shared ``BlockAllocator`` pool: the same
    interface a `GenerateEngine` allocates through, bounded by `quota`
    DISTINCT physical blocks. Multiple tenants resident in one process
    (ModelFleet) each hold a view over the one pool sized to the real
    HBM budget; a tenant's admission/growth then competes only inside
    its quota and the pool's free list — one tenant can never allocate
    the pool empty past its own share.

    Accounting: a view is charged one unit per DISTINCT block it holds
    at least one reference to (extra refs to an owned block — the
    within-tenant prefix-sharing case — consume no additional physical
    blocks and are not double-charged). ``in_use()`` is the tenant's
    footprint, ``capacity`` its quota, ``available()`` the admission
    headroom = min(pool free, quota remaining). Eviction isolation is
    structural: each tenant's `PrefixCache` is built over its own view,
    so ``evict_for`` under one tenant's allocation pressure only ever
    walks (and derefs) that tenant's entries.

    Every view method runs under the POOL's reentrant lock (the quota
    check and the pool mutation must be one atomic step — two tenants'
    decode threads race on the same free list otherwise)."""

    def __init__(self, pool, quota, tenant=None):
        quota = int(quota)
        if quota < 1:
            raise ValueError("block quota must be >= 1, got %d" % quota)
        self.pool = pool
        self.quota = quota
        self.tenant = tenant
        self.block_size = pool.block_size
        self.lock = pool.lock
        self._held = {}         # block id -> refs held through this view

    @property
    def capacity(self):
        return min(self.quota, self.pool.capacity)

    def available(self):
        with self.lock:
            return max(0, min(self.pool.available(),
                              self.quota - len(self._held)))

    def in_use(self):
        with self.lock:
            return len(self._held)

    def refcount(self, bid):
        return self.pool.refcount(bid)

    def alloc(self, n):
        with self.lock:
            if len(self._held) + n > self.quota:
                return None
            out = self.pool.alloc(n)
            if out is not None:
                for b in out:
                    self._held[b] = 1
            return out

    def ref(self, bid):
        with self.lock:
            if bid not in self._held and len(self._held) >= self.quota:
                raise ValueError(
                    "ref of block %d would exceed tenant %r quota %d"
                    % (bid, self.tenant, self.quota))
            self.pool.ref(bid)
            self._held[bid] = self._held.get(bid, 0) + 1

    def deref(self, bid):
        with self.lock:
            held = self._held.get(bid, 0)
            if held < 1:
                raise ValueError(
                    "deref of block %d not held by tenant %r"
                    % (bid, self.tenant))
            if held == 1:
                del self._held[bid]
            else:
                self._held[bid] = held - 1
            return self.pool.deref(bid)

    def deref_many(self, bids):
        with self.lock:
            freed = 0
            for b in bids:
                if self.deref(b):
                    freed += 1
            return freed


class PrefixCache(object):
    """hash-chain -> physical block map with LRU pressure eviction.

    Each registered block carries ONE cache reference (so it outlives
    its creator request). `match` walks the chain from depth 0 and
    returns the longest cached run; `evict_for` releases stale entries
    — least-recently-used first, deepest entry first within a tie, so a
    chain never loses a shallow link before its deeper ones — until the
    allocator can satisfy a request, and is only called under
    allocation pressure.

    `side`: the allocator of a second pool (the window layers':
    `WindowRings.blocks`; the state-space layers' snapshot rows:
    `SlotRows.blocks`) whose block of the same logical block an entry
    may hold beside its own, one reference each, with the first row of it
    that was written (a snapshot row is the state as of the block's LAST
    row and holds nothing else). A request resumes at depth ``d`` only where
    the entries before it still hold the side blocks of the ``reach`` rows
    before row ``d * block_size`` (`side_run`). The side's blocks go under
    the side's own pressure (`evict_side_for`): least recently used first
    as well, but the SHALLOWEST first within a tie -- a resume needs the
    blocks just before its depth and none of those further up -- and the
    entry stays, for the chain and for a later tenant's block
    (`register`)."""

    def __init__(self, alloc, side=None):
        self._alloc = alloc
        self._side = side
        # hash -> [block_id, depth, last_used, side id or None, its first row]
        self._entries = {}
        self._clock = 0

    def __len__(self):
        return len(self._entries)

    def match(self, hashes):
        """Longest cached prefix run for `hashes` (chain order): the
        list of physical block ids, NOT yet referenced — the caller
        refs the ones it keeps."""
        self._clock += 1
        out = []
        for i, h in enumerate(hashes):
            e = self._entries.get(h)
            if e is None or e[1] != i:      # depth-checked: chains only
                break                       # ever match from the root
            e[2] = self._clock
            out.append(e[0])
        return out

    def side_run(self, hashes, depth, reach):
        """(d, ids): the deepest ``d <= depth`` at which a request can
        resume over the side pool, and the side's blocks of the `reach`
        rows before row ``d * block_size`` (logical blocks ``d - len(ids)
        .. d - 1``), NOT yet referenced. A shallower depth than the chain's
        still saves its share of the prefill; (0, []) is a miss. `hashes`
        ``[:depth]`` are entries (`match` returned that many)."""
        bs = self._alloc.block_size
        for d in range(depth, 0, -1):
            row = max(0, d * bs - reach)
            ids = []
            for b in range(row // bs, d):
                e = self._entries[hashes[b]]
                if e[3] is None or e[4] > max(0, row - b * bs):
                    break
                ids.append(e[3])
            else:
                return d, ids
        return 0, []

    def has_side(self, h):
        """Whether hash `h` has an entry that holds a side block."""
        e = self._entries.get(h)
        return e is not None and e[3] is not None

    def register(self, h, depth, block_id, side=None):
        """Publish `block_id` as the home of chain hash `h` (depth =
        its block index within the prompt). First writer wins — an
        already-registered hash keeps its existing block. `side`: (the
        side pool's block of the same rows, the first row of it that is
        written); an entry that holds none, or one written from a later
        row on, takes it."""
        e, new = self._entries.get(h), False
        if e is None:
            self._clock += 1
            self._alloc.ref(block_id)
            e = self._entries[h] = [block_id, int(depth), self._clock,
                                    None, 0]
            new = True
        if side is not None and (e[3] is None or side[1] < e[4]):
            self._side.ref(side[0])
            if e[3] is not None:
                self._side.deref(e[3])
            e[3], e[4] = side
        return new

    def _drop(self, h):
        e = self._entries.pop(h)
        self._alloc.deref(e[0])
        if e[3] is not None:
            self._side.deref(e[3])

    def evict_for(self, n_needed):
        """Drop cache-only entries (block refcount 1 — no live slot)
        until the allocator has `n_needed` free blocks. Returns the
        number of entries evicted."""
        if self._alloc.available() >= n_needed:
            return 0
        victims = sorted(self._entries.items(),
                         key=lambda kv: (kv[1][2], -kv[1][1]))
        evicted = 0
        for h, e in victims:
            if self._alloc.available() >= n_needed:
                break
            if self._alloc.refcount(e[0]) == 1:   # only the cache holds it
                self._drop(h)
                evicted += 1
        return evicted

    def evict_side_for(self, n_needed):
        """Let go of side blocks that the cache alone holds until the
        side's allocator has `n_needed` free. Returns how many went."""
        evicted = 0
        if self._side.available() >= n_needed:
            return evicted
        for e in sorted((e for e in self._entries.values()
                         if e[3] is not None),
                        key=lambda e: (e[2], e[1])):
            if self._side.available() >= n_needed:
                break
            if self._side.refcount(e[3]) == 1:
                self._side.deref(e[3])
                e[3] = None
                evicted += 1
        return evicted

    def drop_all(self):
        """Release every cached entry (engine shutdown)."""
        for h in list(self._entries):
            self._drop(h)
