"""What every family's cell is: the program's trainer with the handles the
harness needs, whatever the model."""

from __future__ import annotations

import dataclasses
import gc


def seed32(seed):
    """The seed as the program's ``args.seed`` takes it (it seeds numpy's
    and JAX's generators, which want 32 bits)."""
    return int(seed) % (2 ** 31 - 1)


def nest(flat):
    """``{"a/b/c": leaf}`` -> ``{"a": {"b": {"c": leaf}}}``: the
    reference's canonical names as the program's tree."""
    tree = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


@dataclasses.dataclass
class Cell:
    """``api.train_one_round()`` is what the window drives."""
    api: object
    ns: list
    traffic: dict
    seed32: int
    work_per_round: dict
    shapes: dict
    #: the collection of ``api.global_state`` that is compared, or None
    #: for the whole state
    state_key: str | None
    #: ``feed_fn(ns, traffic, seed32, rounds, backend)``: the family's
    #: feed rule
    feed_fn: object
    #: which schedule generator the program said it runs (``feed.py``)
    feed_backend: str

    def snapshot(self):
        """The compared state now, on the host: canonical name -> array."""
        import jax

        state = self.api.global_state
        state = dict(state) if self.state_key is None \
            else state[self.state_key]
        flat = jax.tree_util.tree_flatten_with_path(state)[0]
        host = jax.device_get([leaf for _, leaf in flat])
        return {"/".join(str(getattr(k, "key", k)) for k in path): arr
                for (path, _), arr in zip(flat, host)}

    def feed(self, rounds):
        return self.feed_fn(self.ns, self.traffic, self.seed32, rounds,
                            self.feed_backend)

    def free(self):
        self.api = None
        gc.collect()
