"""DCGAN generator/discriminator + updater (BASELINE config #5).

Reference capability: ChainerMN ``examples/dcgan/train_dcgan.py`` (CIFAR
DCGAN with multi-node optimizers for both networks).  TPU-first: both
adversarial updates run as compiled steps; the generator's noise is an
explicit PRNG key argument (idiomatic-JAX replacement for hidden RNG
state).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.link import Chain
from ..core import reporter
from ..nn import functions as F
from ..nn import links as L
from ..training.updaters import StandardUpdater

__all__ = ["Generator", "Discriminator", "DCGANUpdater"]


class Generator(Chain):
    """z [B, n_hidden] → image [B, 3, 32, 32]."""

    def __init__(self, n_hidden=128, ch=256, bottom_width=4, seed=0):
        super().__init__()
        self.n_hidden = n_hidden
        self.ch = ch
        self.bottom_width = bottom_width
        with self.init_scope():
            self.l0 = L.Linear(n_hidden, bottom_width * bottom_width * ch,
                               seed=seed)
            self.bn0 = L.BatchNormalization(bottom_width * bottom_width * ch)
            self.dc1 = L.Deconvolution2D(ch, ch // 2, 4, stride=2, pad=1,
                                         seed=seed + 1)
            self.bn1 = L.BatchNormalization(ch // 2)
            self.dc2 = L.Deconvolution2D(ch // 2, ch // 4, 4, stride=2,
                                         pad=1, seed=seed + 2)
            self.bn2 = L.BatchNormalization(ch // 4)
            self.dc3 = L.Deconvolution2D(ch // 4, ch // 8, 4, stride=2,
                                         pad=1, seed=seed + 3)
            self.bn3 = L.BatchNormalization(ch // 8)
            self.dc4 = L.Deconvolution2D(ch // 8, 3, 3, stride=1, pad=1,
                                         seed=seed + 4)

    def make_hidden(self, batchsize, key=None):
        if key is None:
            key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        return jax.random.normal(key, (batchsize, self.n_hidden),
                                 jnp.float32)

    def forward(self, z):
        h = F.relu(self.bn0(self.l0(z)))
        h = h.reshape(-1, self.ch, self.bottom_width, self.bottom_width)
        h = F.relu(self.bn1(self.dc1(h)))
        h = F.relu(self.bn2(self.dc2(h)))
        h = F.relu(self.bn3(self.dc3(h)))
        return F.tanh(self.dc4(h))


class Discriminator(Chain):
    def __init__(self, ch=256, seed=100):
        super().__init__()
        with self.init_scope():
            self.c0 = L.Convolution2D(3, ch // 4, 3, stride=1, pad=1,
                                      seed=seed)
            self.c1 = L.Convolution2D(ch // 4, ch // 2, 4, stride=2, pad=1,
                                      seed=seed + 1)
            self.bn1 = L.BatchNormalization(ch // 2)
            self.c2 = L.Convolution2D(ch // 2, ch, 4, stride=2, pad=1,
                                      seed=seed + 2)
            self.bn2 = L.BatchNormalization(ch)
            self.l4 = L.Linear(ch * 8 * 8, 1, seed=seed + 3)

    def forward(self, x):
        h = F.leaky_relu(self.c0(x))
        h = F.leaky_relu(self.bn1(self.c1(h)))
        h = F.leaky_relu(self.bn2(self.c2(h)))
        return self.l4(h.reshape(h.shape[0], -1))


class DCGANUpdater(StandardUpdater):
    """Adversarial updater (reference: the dcgan example's custom updater).

    Both networks' parameters must be *traced arguments* of one compiled
    step — updating them alternately through two independent jitted losses
    would bake the opposite network's weights as stale constants.  Each
    iteration therefore runs ONE program: discriminator grads → dis
    update → generator grads against the updated discriminator → gen
    update (the reference's sequential semantics).  When the optimizers
    are multi-node wrappers, the step is shard_mapped over the
    communicator axis with the real batch sharded and both nets' grads
    pmean'd — data-parallel GAN for free.
    """

    def __init__(self, iterator, opt_gen, opt_dis, seed=0, **kwargs):
        super().__init__(iterator,
                         {"gen": opt_gen, "dis": opt_dis}, **kwargs)
        self._key = jax.random.PRNGKey(seed)
        self._gan_step = None

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _actual(self, name):
        opt = self._optimizers[name]
        return getattr(opt, "actual_optimizer", opt)

    def _communicator(self):
        opt = self._optimizers["dis"]
        comm = getattr(opt, "communicator", None)
        return comm if comm is not None and comm.axis_name is not None \
            else None

    def _build_step(self):
        from ..core.optimizer import apply_transform_update
        gen = self._actual("gen").target
        dis = self._actual("dis").target
        tx_gen = self._actual("gen")._transform()
        tx_dis = self._actual("dis")._transform()
        comm = self._communicator()
        from ..core.link import bind_state

        def losses(gen_params, dis_params, pstate_gen, pstate_dis,
                   x_real, z):
            def dis_loss(dp):
                with bind_state(gen, {"params": gen_params,
                                      "state": pstate_gen}) as hg:
                    with bind_state(dis, {"params": dp,
                                          "state": pstate_dis}) as hd:
                        y_real = dis(x_real)
                        x_fake = gen(z)
                        y_fake = dis(jax.lax.stop_gradient(x_fake))
                        loss = F.sigmoid_cross_entropy(
                            y_real, jnp.ones_like(y_real, jnp.int32)) + \
                            F.sigmoid_cross_entropy(
                                y_fake, jnp.zeros_like(y_fake, jnp.int32))
                        new_pd = hd.collect()
                return loss, new_pd

            def gen_loss(gp, dis_params_now):
                with bind_state(gen, {"params": gp,
                                      "state": pstate_gen}) as hg:
                    with bind_state(dis, {"params": dis_params_now,
                                          "state": pstate_dis}):
                        x_fake = gen(z)
                        y_fake = dis(x_fake)
                        loss = F.sigmoid_cross_entropy(
                            y_fake, jnp.ones_like(y_fake, jnp.int32))
                        new_pg = hg.collect()
                return loss, new_pg

            return dis_loss, gen_loss

        def step(gen_state, dis_state, opt_gen_state, opt_dis_state,
                 hyper_gen, hyper_dis, x_real, z):
            gen_params, pstate_gen = gen_state
            dis_params, pstate_dis = dis_state
            dis_loss, gen_loss = losses(gen_params, dis_params, pstate_gen,
                                        pstate_dis, x_real, z)
            (l_dis, new_pd), g_dis = jax.value_and_grad(
                dis_loss, has_aux=True)(dis_params)
            if comm is not None:
                g_dis = comm.grad_transform()(g_dis)
            new_dis_params, new_opt_dis = apply_transform_update(
                tx_dis, g_dis, opt_dis_state, dis_params, hyper_dis["lr"],
                hyper_dis.get("decoupled_wd", 0.0))
            (l_gen, new_pg), g_gen = jax.value_and_grad(
                gen_loss, has_aux=True)(gen_params, new_dis_params)
            if comm is not None:
                g_gen = comm.grad_transform()(g_gen)
            new_gen_params, new_opt_gen = apply_transform_update(
                tx_gen, g_gen, opt_gen_state, gen_params, hyper_gen["lr"],
                hyper_gen.get("decoupled_wd", 0.0))
            out = ((new_gen_params, new_pg), (new_dis_params, new_pd),
                   new_opt_gen, new_opt_dis, l_gen, l_dis)
            if comm is not None:
                from jax import lax as jlax
                out = (out[0], out[1], out[2], out[3],
                       jlax.pmean(l_gen, comm.axis_name),
                       jlax.pmean(l_dis, comm.axis_name))
            return out

        if comm is None:
            # donate optimizer states (replaced by returned values)
            return jax.jit(step, donate_argnums=(2, 3))
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mapped = shard_map(
            step, mesh=comm.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(),
                      P(comm.axis_name), P(comm.axis_name)),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(2, 3))

    def update_core(self):
        from ..core.link import extract_state
        gen_opt, dis_opt = self._actual("gen"), self._actual("dis")
        gen, dis = gen_opt.target, dis_opt.target
        batch = self._iterators["main"].next()
        x_real = self.converter(batch, self.device)
        if isinstance(x_real, tuple):
            x_real = x_real[0]
        x_real = jnp.asarray(x_real)
        z = gen.make_hidden(x_real.shape[0], key=self._next_key())

        sg, sd = extract_state(gen), extract_state(dis)
        opt_gen_state = gen_opt._ensure_opt_state(sg["params"])
        opt_dis_state = dis_opt._ensure_opt_state(sd["params"])
        if self._gan_step is None:
            self._gan_step = self._build_step()
        (new_gen, new_pg), (new_dis, new_pd), new_og, new_od, l_gen, l_dis = \
            self._gan_step((sg["params"], sg["state"]),
                           (sd["params"], sd["state"]),
                           opt_gen_state, opt_dis_state,
                           gen_opt._hyper_values(), dis_opt._hyper_values(),
                           x_real, z)
        gen_opt._write_back(new_gen, new_pg)
        dis_opt._write_back(new_dis, new_pd)
        gen_opt._opt_state = new_og
        dis_opt._opt_state = new_od
        gen_opt.t += 1
        dis_opt.t += 1
        reporter.report({"gen/loss": float(l_gen), "dis/loss": float(l_dis)})
        if self.is_new_epoch:
            for opt in self._optimizers.values():
                opt.new_epoch()
