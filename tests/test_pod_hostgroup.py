"""Host groups (docs/distributed.md): the pod tier's layout authority.

- **layout**: ``host_major_slices`` is the ONE deal shared by the flat
  multihost mesh axis and the pod's per-host shard meshes — contiguous
  per-host blocks for single-process device lists, process-grouped for
  real ``jax.distributed`` worlds;
- **drivers**: the sim driver slices the in-process virtual-device mesh
  into H synthetic hosts (the CPU-CI path every pod test runs on); the
  distributed driver demands a real multi-process world and raises
  :class:`PodUnsupported` — with the capability probe's machine-readable
  reason — anywhere it cannot run (tests skip, not fail).
"""

import pytest

from geomesa_tpu import conf
from geomesa_tpu.parallel.mesh import host_major_slices
from geomesa_tpu.pod import PodUnsupported, make_host_group, probe_capability


class _Dev:
    """jax.Device stand-in: just the attributes the layout code reads."""

    def __init__(self, i, proc=0):
        self.id = i
        self.process_index = proc

    def __repr__(self):
        return f"d{self.id}@p{self.process_index}"


class TestHostMajorSlices:
    def test_single_process_slices_are_contiguous(self):
        devs = [_Dev(i) for i in range(8)]
        slices = host_major_slices(devs, 4, 2)
        assert [[d.id for d in s] for s in slices] == [
            [0, 1], [2, 3], [4, 5], [6, 7]
        ]

    def test_multi_process_groups_by_process(self):
        # a real pod: device ids interleave but process_index decides
        devs = [_Dev(0, 0), _Dev(2, 1), _Dev(1, 0), _Dev(3, 1)]
        slices = host_major_slices(devs, 2, 2)
        assert [[d.process_index for d in s] for s in slices] == [[0, 0], [1, 1]]
        assert [[d.id for d in s] for s in slices] == [[0, 1], [2, 3]]

    def test_flat_mesh_and_pod_slices_agree(self):
        """The pod's per-host slices concatenate to EXACTLY the flat
        host-major mesh order — the two views never disagree on which
        host owns which device (shard h of the pod == contiguous
        device block h of the flat mesh)."""
        import jax

        devs = jax.devices()
        group = make_host_group(hosts=4, devices_per_host=2, driver="sim")
        flat = [d for s in group.device_slices for d in s]
        assert flat == list(group.flat_mesh().devices.flatten())
        assert flat == devs[:8]


class TestSimDriver:
    def test_slices_and_meshes(self):
        group = make_host_group(hosts=4, devices_per_host=2, driver="sim")
        assert group.driver == "sim"
        assert (group.hosts, group.devices_per_host) == (4, 2)
        for h in range(4):
            m = group.mesh(h)
            assert list(m.devices.flatten()) == list(group.device_slices[h])
            assert m is group.mesh(h)  # cached

    def test_dph_defaults_to_even_split(self):
        group = make_host_group(hosts=2, driver="sim")
        assert group.devices_per_host == 4  # 8 virtual devices / 2

    def test_needs_explicit_host_count(self):
        with pytest.raises(ValueError, match="host count"):
            make_host_group(driver="sim")

    def test_too_few_devices_is_unsupported(self):
        with pytest.raises(PodUnsupported, match="devices"):
            make_host_group(hosts=64, driver="sim")

    def test_knob_resolution(self):
        """geomesa.pod.hosts / .devices.per.host / .driver settle the
        group when the call site passes nothing (docs/config.md)."""
        conf.POD_HOSTS.set(2)
        conf.POD_DEVICES_PER_HOST.set(3)
        conf.POD_DRIVER.set("sim")
        try:
            group = make_host_group()
            assert (group.hosts, group.devices_per_host) == (2, 3)
        finally:
            conf.POD_HOSTS.clear()
            conf.POD_DEVICES_PER_HOST.clear()
            conf.POD_DRIVER.clear()

    def test_unknown_driver_rejected(self):
        with pytest.raises(ValueError, match="driver"):
            make_host_group(hosts=2, driver="nope")


class TestDistributedDriver:
    def test_probe_verdict_is_machine_readable(self):
        v = probe_capability()
        assert v["verdict"] in ("supported", "UNSUPPORTED", "error")
        assert isinstance(v["supported"], bool)
        assert v["supported"] == (v["verdict"] == "supported")
        assert "reason" in v

    def test_single_process_raises_pod_unsupported(self):
        """A single-process world can never run the distributed driver:
        either the backend has no multi-process collectives (the CPU CI
        verdict) or the process wasn't launched under jax.distributed.
        Both surface as PodUnsupported — the skip-not-fail contract the
        differential matrix keys off."""
        with pytest.raises(PodUnsupported):
            make_host_group(driver="distributed")
