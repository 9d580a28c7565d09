"""The timed path of a configuration whose query is one IR plan over one
resident partition: tables made on the device from the seed, and one query =
``plan.compile_plan(plan, inputs)(inputs)``, ``block_until_ready``, the live
rows to the host.  A configuration's module hands this its recipe
(``make_partition``, ``make_shared``, ``plan``, ``RESULT_COLUMNS``); the
``served`` entry runs it inside the worker, the ``inproc`` entry in the
process that holds the chip."""

import numpy as np

from benchmark import lib


class PlanState:
    def __init__(self, cfg, mod, seed, devs):
        import jax

        self.cfg, self.mod, self.devs = cfg, mod, devs
        self.rows = 1 << int(cfg["log2_rows"])
        self.partitions = int(cfg["partitions"])
        self.plan = mod.plan(cfg)
        key = jax.random.PRNGKey(lib.seed_words(seed, 1)[0] & 0x7FFFFFFF)
        # one program makes every partition: the index is an argument
        self._gen = jax.jit(lambda kk, part: mod.make_partition(
            cfg, jax.random.fold_in(kk, part), self.rows))
        self._shared = jax.jit(
            lambda kk: mod.make_shared(cfg, jax.random.fold_in(kk, 1 << 20),
                                       self.rows))
        cap = int(cfg["result_capacity"])
        self._head = jax.jit(lambda res: jax.tree_util.tree_map(
            lambda a: a[:cap], res))
        with jax.default_device(devs[0]):
            shared = self._shared(key)
            self.inputs = [dict(self._gen(key, np.int32(p)), **shared)
                           for p in range(self.partitions)]
            jax.block_until_ready(self.inputs)

    def table_bytes(self):
        import jax

        seen, total = set(), 0
        for leaf in jax.tree_util.tree_leaves(self.inputs):
            if id(leaf) not in seen:
                seen.add(id(leaf))
                total += leaf.nbytes
        return total

    def query(self, part, q, spans, inputs=None):
        """One query over partition ``part``; returns name -> (data,
        validity, spark type) of the live rows, on the host."""
        import jax

        from spark_rapids_jni_tpu import plan

        inputs = self.inputs[part] if inputs is None else inputs
        with spans.span(q, "lookup"):
            cp = plan.compile_plan(self.plan, inputs)
        with spans.span(q, "execute"):
            res, ng = jax.block_until_ready(cp(inputs))
        with spans.span(q, "result"):
            cap = int(self.cfg["result_capacity"])
            # a result the plan left longer than the capacity is cut on the
            # device first.  One that fits leaves as it is: a second program
            # would queue behind the other caller's query, and two callers
            # would then answer together and leave the device idle together
            if any(a.shape[0] > cap for a in jax.tree_util.tree_leaves(res)):
                res = self._head(res)
            # the head and the group count in one transfer (the count handed
            # through _head cost q95 1.3%: PERF.md section 6, PR 30)
            small, n = jax.device_get((res, ng))
            n = int(n)
            if n > cap:
                raise lib.BenchError(f"{n} groups, result_capacity {cap}")
            return {c: (np.asarray(small[c].data)[:n],
                        np.asarray(small[c].validity)[:n], small[c].dtype)
                    for c in self.mod.RESULT_COLUMNS}

    def _start_copies(self, part):
        import jax

        for leaf in jax.tree_util.tree_leaves(self.inputs[part]):
            leaf.copy_to_host_async()

    def host_tables(self, part):
        """Partition ``part`` as numpy columns, for the reference."""
        self._start_copies(part)   # all under way before the first is read
        out = {}
        for name, batch in self.inputs[part].items():
            for col in batch.names:
                out[f"{name}.{col}"] = np.asarray(batch[col].data)
                if not np.asarray(batch[col].validity).all():
                    raise lib.BenchError(f"null in generated {name}.{col}")
        return out

    def tables_in_turn(self, parts):
        """(part, its numpy columns) for each of ``parts``, and the device's
        tables freed after the last.  One partition's host copy at a time,
        the next one's on its way while the reference reads this one: eight
        batches of the plugin's size are 6 GB that no run needs on the host
        at once."""
        for i, part in enumerate(parts):
            for nxt in parts[i + 1:i + 2]:
                self._start_copies(nxt)
            yield part, self.host_tables(part)
            self.inputs[part] = None   # the array keeps its host copy
        self.free()

    def free(self):
        self.inputs = None


def build(cfg, mod, seed, devs):
    return PlanState(cfg, mod, seed, devs)


def plain(result):
    """name -> data of a query's result, nulls counted."""
    cols = {c: np.asarray(d) for c, (d, _v, _t) in result.items()}
    nulls = int(sum((~np.asarray(v)).sum() for _d, v, _t in result.values()))
    return cols, nulls


def compare_exact(got, want, key, columns):
    """Values that differ from the reference, groups matched on ``key``; a
    group that is missing, extra or given twice counts once."""
    order = np.argsort(got[key], kind="stable")
    g = {c: np.asarray(got[c])[order].astype(np.int64) for c in columns}
    w = {c: np.asarray(want[c]).astype(np.int64) for c in columns}
    both, gi, wi = np.intersect1d(g[key], w[key], return_indices=True)
    wrong = (len(g[key]) - len(both)) + (len(w[key]) - len(both))
    for c in columns:
        wrong += int((g[c][gi] != w[c][wi]).sum())
    return int(wrong)
