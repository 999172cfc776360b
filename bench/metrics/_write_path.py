"""Programs of the write path, by the names the device trace gives their
modules: the batch pool's sort and dedup (``flat_ctree.from_device``)
and the mirror's rank-merge insert and delete (``flat_graph``)."""
MODULES = [r"^jit_insert_edges$", r"^jit_delete_edges$", r"^jit_from_device$"]


def runs(summary):
    return summary.module_runs(MODULES)
