"""Weights from `--seed`, made by the benchmark on the device in one jitted
call, float32 (the type the program trains in).

A family states its parameters as `{path: (shape, init)}` with `init` one of
`("normal", std)` or `("const", value)`; a leaf's values depend on the seed
and its path alone.  The program's state and the plain reference are both
given what `make` returns: neither takes anything the other has made.
"""
import zlib


def seed_key(seed):
    """A key from any whole number a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make(seed, shapes, sharding=None):
    """`{path: float32 array}` on the device (replicated over `sharding`'s
    mesh where one is given).  Leaves of one shape are drawn together, one
    key a leaf, so the program is a handful of generators and not one for
    each of some hundreds of leaves (which took 91 s to compile and 22 s to
    load for GPT-2 large; chip run, PR 25)."""
    import jax
    import jax.numpy as jnp

    classes = {}
    for p in sorted(shapes):
        shape, (kind, value) = shapes[p]
        if kind not in ("normal", "const"):
            raise ValueError(f"{p}: unknown init {kind!r}")
        classes.setdefault((tuple(shape), kind), []).append(p)

    def gen(key):
        out = {}
        for (shape, kind), paths in classes.items():
            if kind == "const":
                for p in paths:
                    out[p] = jnp.full(shape, shapes[p][1][1], jnp.float32)
                continue
            ids = jnp.asarray([zlib.crc32(p.encode()) & 0x7FFFFFFF
                               for p in paths], jnp.uint32)
            draws = jax.vmap(lambda i: jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))(ids)
            for j, p in enumerate(paths):
                out[p] = draws[j] * shapes[p][1][1]
        return out

    return jax.jit(gen, out_shardings=sharding)(seed_key(seed))


def nest(flat):
    """`{"a/b/c": x}` -> `{"a": {"b": {"c": x}}}`, the program's tree."""
    tree = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def flatten(tree, prefix=""):
    """The inverse of `nest` for a tree of dicts."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out
