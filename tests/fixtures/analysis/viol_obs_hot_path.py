"""Seeded violations: telemetry mutations inside a traced scope.

The repro.obs API is host-side Python; from jitted code each call below
either records a trace-time constant (once, at trace time — not per
step) or would need a host callback to mean anything. The loop records
at the host boundary after the step returns.
"""
import jax
import jax.numpy as jnp

from repro import obs as obs_mod

obs = obs_mod.Obs(trace=True, timeline=True)


def step(x):
    y = jnp.tanh(x)
    obs.registry.counter("fixture_steps_total", "hot").inc()  # LINT: obs-no-hot-path-sync
    obs.tracer.instant("mid_step", "train")  # LINT: obs-no-hot-path-sync
    obs.event("fixture_event", val=1.0)  # LINT: obs-no-hot-path-sync
    obs.timeline.record_serve(0, occupancy=0.5)  # LINT: obs-no-hot-path-sync
    with obs.span("serve.step"):  # LINT: obs-no-hot-path-sync
        y = y + 1
    return y


out = jax.jit(step)(jnp.zeros((4,)))


def host_report(dt):
    # NOT traced: recording after the jitted step returned is the point.
    obs.registry.histogram("fixture_step_seconds", "wall",
                           unit="s").observe(dt)
    obs.tracer.complete("step", "train", dt)
