"""Installers that break the timed path underneath the harness, one fault
each, for the tests that see ``correct`` come out false. Each plants its
fault in the endpoint process, then installs as the benchmark does."""
import itertools

from chipbench import remote


def token(registry):
    """A served token altered where it is produced: every third sampled
    token is replaced by its neighbour id."""
    from repro.serve import sampler

    sample, calls = sampler.sample, itertools.count()

    def altered(logits, key, temperature=0.0, top_k=0):
        tok = sample(logits, key, temperature, top_k)
        return (tok + 1) % 128 if next(calls) % 3 == 1 else tok

    sampler.sample = altered
    return remote.install(registry)


def answer(registry):
    """A prefill's answer altered where it is produced."""
    from repro.serve import fabric

    serve = fabric.serve_prefill

    def altered(data, env):
        out = serve(data, env)
        out["next_token"] = (out["next_token"] + 1) % 128
        return out

    fabric.serve_prefill = altered
    return remote.install(registry)


def padded_row(registry):
    """A served token moved onto a padded row of the table (the first id
    past the vocabulary), where it is produced: every third answer."""
    from repro.serve import fabric

    calls = itertools.count()

    def wrap(serve, key):
        def altered(data, env):
            out = serve(data, env)
            if next(calls) % 3 == 1:
                tok = out[key].copy()
                tok.reshape(-1)[-1] = env["cfg"].vocab_size
                out[key] = tok
            return out
        return altered

    fabric.serve_prefill = wrap(fabric.serve_prefill, "next_token")
    fabric.serve_generate = wrap(fabric.serve_generate, "tokens")
    return remote.install(registry)


def frozen_state(registry):
    """A decode step that returns its state unchanged: the cache (KV or
    recurrent state) never advances past the prompt."""
    from repro.serve import fabric

    build = fabric.build_steps

    def frozen_build(model, bucket):
        prefill, decode = build(model, bucket)

        def frozen(params, cache, batch):
            return decode(params, cache, batch)[0], cache

        return prefill, frozen

    fabric.build_steps = frozen_build
    return remote.install(registry)
