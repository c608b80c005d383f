"""Driver for traffic of kind "fedpipe": the paper's three-stage federated
pipeline, ``launch.train.FedPipeline.run_pipeline``, one client per chip.

One unit of work is one pipeline iteration (stage-1 round and aggregation,
stage 2 on ΔA_D, stage 3 on ΔB_M), and the host blocks once at its end.
Set-up builds the pipeline, the weights and the client state from the
seed, and runs the first iteration through ``run_pipeline``: that compiles
every stage program, and its readings are what ``check`` compares with
the reference once the window has closed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import checks
import counts
import reference
import weights
from dims import Dims, program_config

STAGES = ("round", "global", "personal")


def _spanned(name, fn):
    """``fn`` inside a host span the trace attributes idle gaps to."""
    def call(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kw)
    return call


class Cell:
    def __init__(self, d: Dims, traffic: dict, seed: int, chips: int,
                 log=print):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_client_mesh
        from repro.launch.train import TrainSettings, make_fed_pipeline_step

        job = traffic["job"]
        C = traffic["clients"]
        if C != chips:
            raise ValueError(f"traffic has {C} clients for {chips} chips: "
                             "one client per chip")
        self.d, self.job, self.C, self.log = d, job, C, log
        S, rows = job["seq_len"], job["rows"]
        self.tokens_per_unit = S * rows * C * (
            job["local_steps"] + job["global_steps"] + job["personal_steps"])
        self.flops_per_unit = sum(
            n * counts.train_flops(d, r, S) for n, r in (
                (C * job["local_steps"], rows),
                (job["global_steps"], rows * C),
                (C * job["personal_steps"], rows)))
        mesh = make_client_mesh(C)
        self.mesh = mesh
        client = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())
        st = TrainSettings(
            lr=job["lr"], micro_batches=1, clip=job["clip"],
            method=job["method"], local_steps=job["local_steps"],
            global_steps=job["global_steps"],
            personal_steps=job["personal_steps"], server_lr=job["server_lr"],
            lam=job["lam"])
        pipe = make_fed_pipeline_step(program_config(d), mesh, st)
        self.pipe = dataclasses.replace(
            pipe, round_step=_spanned("bench/stage1", pipe.round_step),
            global_step=_spanned("bench/stage2", pipe.global_step),
            personal_step=_spanned("bench/stage3", pipe.personal_step))

        key = weights.seed_key(seed)
        k_base, k_ad, k_data = jax.random.split(key, 3)
        self.base = weights.on_device(weights.make_base, k_base, d,
                                      sharding=rep)
        adapters = weights.on_device(weights.make_adapters, k_ad, d, C,
                                     sharding=client)
        self.data = self._make_data(k_data, traffic["data_iterations"],
                                    client, rep)
        # laid out as the round program returns it, so later iterations
        # reuse the first one's compiled program
        opt_state = jax.device_put(self.pipe.opt_init(adapters), client)
        self.theta0 = jax.device_get(adapters)
        self.step = jnp.zeros((), jnp.int32)

        # the first iteration: compiles every stage; its readings are
        # checked against the reference after the window
        self.adapters, self.opt_state, met = self._iterate(
            adapters, opt_state, 0)
        jax.block_until_ready(self.adapters)
        self.first = {"ce": tuple(float(met[s]["ce"]) for s in STAGES),
                      "mu1": jax.device_get(self.opt_state.mu),
                      "adapters": jax.device_get(self.adapters)}
        self.next = 1
        self.losses: list = []      # stage losses of each timed iteration
        log(f"fedpipe: {C} client(s), {rows}x{S} tokens per step, steps "
            f"{job['local_steps']}/{job['global_steps']}/"
            f"{job['personal_steps']}, first iteration ce "
            + " ".join(f"{v:.6f}" for v in self.first["ce"]))

    def _make_data(self, key, n, client, rep):
        """``n`` iterations of token rows, drawn on the device: every row
        differs, and the same seed gives the same rows."""
        job, C, d = self.job, self.C, self.d
        S, rows = job["seq_len"], job["rows"]
        shapes = {"batch": (n, C, job["local_steps"] * rows, S),
                  "server": (n, job["global_steps"] * rows * C, S),
                  "personal": (n, C, job["personal_steps"] * rows, S)}
        ks = dict(zip(shapes, jax.random.split(key, len(shapes))))
        made = jax.jit(lambda: {
            k: jax.random.randint(ks[k], s, 5, d.vocab, jnp.int32)
            for k, s in shapes.items()})()
        out = []
        for i in range(n):
            it = {}
            for k, sh in (("batch", client), ("server", rep),
                          ("personal", client)):
                tok = jax.device_put(made[k][i], sh)
                it[k] = {"tokens": tok,
                         "loss_mask": jax.device_put(
                             jnp.ones(tok.shape, jnp.float32), sh)}
            out.append(it)
        return out

    def _iterate(self, adapters, opt_state, i):
        b = self.data[i % len(self.data)]
        adapters, opt_state, _, _, met = self.pipe.run_pipeline(
            self.base, adapters, opt_state, self.step, b["batch"],
            b["server"], b["personal"])
        self.step = self.step + self.job["local_steps"]
        return adapters, opt_state, met

    def unit(self) -> int:
        """One pipeline iteration, to completion; returns tokens trained."""
        with jax.profiler.TraceAnnotation("bench/iteration"):
            self.adapters, self.opt_state, met = self._iterate(
                self.adapters, self.opt_state, self.next)
            jax.block_until_ready(self.adapters)
        self.next += 1
        self.losses.append([met[s]["ce"] for s in STAGES])
        return self.tokens_per_unit

    @property
    def attempted(self) -> int:
        return len(self.losses)

    @property
    def failed(self) -> int:
        """Iterations with a stage loss that is not finite."""
        return sum(not np.isfinite(np.asarray(ce, np.float64)).all()
                   for ce in self.losses)

    def end_to_end(self, units: int, tokens: int, seconds: float) -> dict:
        return {"train_tokens_per_s": (tokens / seconds, "tokens/s")}

    def free(self):
        """Drop the program's state; the weights stay for the reference."""
        self.losses = jax.device_get(self.losses)
        del self.pipe, self.adapters, self.opt_state
        self.data = self.data[:1]

    def check(self) -> dict:
        """The first iteration against the reference: {number: value}."""
        b = self.data[0]
        ref = reference.pipeline(
            self.base, jax.device_put(self.theta0), b["batch"], b["server"],
            b["personal"], self.d, self.job)
        return checks.train_numbers(self.theta0, self.first,
                                    jax.device_get(ref))
