"""Device self time of the shared expert and of the two projections around the latent experts (scopes `shared_expert`, `latent_down`, `latent_up`) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(
        records, "shared_expert", "latent_down", "latent_up")
