"""Mutation: an ``all_gather`` smuggled onto the delta path.

The mutant is the REAL 2-shard delta cycle plus one extra shard_map'd
all_gather over a row-sharded carry leaf — exactly what an accidental
cross-shard dependency would trace to.  The collective detector must
flag the beat at every shard count.
"""
EXPECT = "jaxpr-delta-collective"


def findings(ctx):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.analysis_static.jaxpr_passes import lint_delta_collectives

    sh = ctx["sharded"]()
    spec, delta = sh["spec"], sh["delta"]

    def mutant(state, carry, queries, updates):
        out = delta(state, carry, queries, updates)
        words = next(iter(carry["scan"].values()))
        gathered = jax.shard_map(
            lambda w: jax.lax.all_gather(w, spec.axis),
            mesh=spec.mesh, in_specs=P(spec.axis),
            out_specs=P(), check_vma=False)(words)
        return out, gathered.sum()

    jx = jax.make_jaxpr(mutant)(*sh["args_delta"])
    return lint_delta_collectives(jx, location="mutant delta")
