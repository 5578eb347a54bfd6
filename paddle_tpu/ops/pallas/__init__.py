"""Hand-written Pallas (Mosaic) kernels, and the one question every
dispatcher in front of them asks."""
import jax

# Axis sizes of each device mesh traced code currently sits under, innermost
# last. The mesh layer reports them (``HybridMesh.__enter__`` / ``__exit__``
# call ``enter_mesh`` / ``exit_mesh``); the kernel layer never looks upward.
_meshes: list[dict] = []


def enter_mesh(axis_sizes: dict) -> None:
    _meshes.append(dict(axis_sizes))


def exit_mesh() -> None:
    _meshes.pop()


def mosaic_kernels_apply() -> bool:
    """Whether a dispatcher takes its Mosaic kernel for the code being
    traced: the backend is a TPU, and XLA is not partitioning that code
    across devices. A Mosaic kernel cannot be partitioned automatically
    (lowering refuses: "wrap the call in a shard_map"), so under a mesh
    the kernels apply only where every axis larger than one is a manual
    (``shard_map``) axis; elsewhere the XLA formulation, which the
    partitioner can split, is the path. Decided from what the trace can
    observe — never from a kernel's failure."""
    if jax.default_backend() != "tpu":
        return False
    if not _meshes:
        return True
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    return all(n == 1 or a in manual for a, n in _meshes[-1].items())
