"""Shared-prefix index: a radix trie over token sequences.

Chat traffic is prefix-heavy — thousands of sessions share one system
prompt, and a new session whose prompt extends a prefix that is already
resident in SOME KV cache only needs to prefill the unshared suffix
(vLLM's automatic-prefix-caching insight; arXiv:2605.25645 prices why
this matters on TPU serving).  Two layers consult this index:

* **Engine-side** (`decode_session.ContinuousBatchingEngine`): keys are
  the prompts of live decode slots, values are slot indices.  Admission
  looks up the longest shared prefix, copies that many cached rows (keys
  and values, or latents: whatever arrays the cache has) out of
  the donor slot (`models.cache_gather_slot`), and chunk-prefills only
  the suffix — prefix-hit TTFT drops to O(suffix) instead of O(prompt).
* **Router-side** (`serve/router.py`): keys are recently-routed session
  prompts, values are replica ids.  New sessions are placed by
  least-occupancy with prefix AFFINITY as the tie-break, so sessions
  sharing a system prompt land where the prefix is hot in the first
  place instead of warming every replica independently.

The trie is a plain compressed-enough radix over int tokens (children
are dicts keyed by the next token), values are opaque owner ids, and
every owner has at most one key — re-inserting an owner replaces its
old key (a reclaimed slot, a replica that moved).  All operations are
O(len(key)); the structure is lock-free by contract (engine thread /
router lock own their instance).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple


class _Node:
    __slots__ = ("children", "owners")

    def __init__(self):
        self.children: Dict[int, "_Node"] = {}
        # owners whose key passes THROUGH this node (id -> key length
        # at which the owner's key ends, if it ends here; 0 otherwise
        # is never stored — we store only terminal depths per owner on
        # the path for O(1) cleanup)
        self.owners: set = set()


class PrefixIndex:
    """Radix/trie shared-prefix index mapping token sequences to owner
    ids (engine slots, replica ids), with longest-match lookup and
    hit/miss accounting."""

    def __init__(self, max_owners: int = 0):
        self._root = _Node()
        self._keys: Dict[Any, Tuple[int, ...]] = {}   # owner -> key
        self._max_owners = int(max_owners)
        self.hits = 0           # lookups that matched >= 1 token
        self.misses = 0
        self.tokens_matched = 0  # total prefix tokens served from hits

    # ------------------------------------------------------------- mutation

    def insert(self, tokens: Iterable[int], owner: Any) -> None:
        """Register ``owner`` as holding ``tokens``.  An owner holds at
        most one key: re-insertion evicts its previous key first (slot
        reuse, replica re-route).  When ``max_owners`` is set, the
        OLDEST owner is evicted past the bound (insertion-ordered dict
        = LRU-by-insert, matching engine slot lifetimes)."""
        key = tuple(int(t) for t in tokens)
        if owner in self._keys:
            self.evict(owner)
        if not key:
            return
        if self._max_owners and len(self._keys) >= self._max_owners:
            oldest = next(iter(self._keys))
            self.evict(oldest)
        node = self._root
        node.owners.add(owner)
        for t in key:
            node = node.children.setdefault(t, _Node())
            node.owners.add(owner)
        self._keys[owner] = key

    def evict(self, owner: Any) -> bool:
        """Drop ``owner``'s key (slot reclaimed, replica gone).  Prunes
        now-ownerless trie branches so memory tracks live owners."""
        key = self._keys.pop(owner, None)
        if key is None:
            return False
        node = self._root
        node.owners.discard(owner)
        path: List[Tuple[_Node, int]] = []
        for t in key:
            nxt = node.children.get(t)
            if nxt is None:       # defensive: trie desynced, stop
                return True
            path.append((node, t))
            node = nxt
            node.owners.discard(owner)
        for parent, t in reversed(path):
            child = parent.children.get(t)
            if child is not None and not child.owners:
                del parent.children[t]
            else:
                break
        return True

    # -------------------------------------------------------------- lookup

    def longest_match(self, tokens: Iterable[int],
                      cap: Optional[int] = None
                      ) -> Tuple[Optional[Any], int]:
        """Walk ``tokens`` down the trie; returns ``(owner, depth)`` for
        the deepest node that still has a live owner (``depth`` = how
        many prefix tokens that owner's key shares with ``tokens``).
        ``cap`` bounds the usable depth (an admission must re-run at
        least the prompt's last token for its logits).  Counts hit/miss
        accounting: a match of zero tokens is a miss."""
        key = [int(t) for t in tokens]
        if cap is not None:
            key = key[:max(0, int(cap))]
        node = self._root
        best: Tuple[Optional[Any], int] = (None, 0)
        depth = 0
        for t in key:
            node = node.children.get(t)
            if node is None:
                break
            depth += 1
            if node.owners:
                best = (next(iter(node.owners)), depth)
        if best[0] is None or best[1] <= 0:
            self.misses += 1
            return (None, 0)
        self.hits += 1
        self.tokens_matched += best[1]
        return best

    # --------------------------------------------------------------- stats

    def __len__(self) -> int:
        return len(self._keys)

    def owners(self) -> List[Any]:
        return list(self._keys)

    def key_of(self, owner: Any) -> Optional[Tuple[int, ...]]:
        return self._keys.get(owner)

    def stats(self) -> Dict[str, Any]:
        total = self.hits + self.misses
        return {"entries": len(self._keys),
                "hits": self.hits, "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else None,
                "tokens_matched": self.tokens_matched}
