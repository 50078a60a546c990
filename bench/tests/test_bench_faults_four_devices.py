"""``bench_faults``' four-device path, on a variant of ``resnet50.b128``
built here (it is no benchmark cell): tiny sizes, four chips, ``ds`` on a
(1, 4) mesh, so that each device holds a quarter of each image's rows and
``parallel/halo`` exchanges the rows at the shards' edges. A CPU test
process sees one device, so ``bench_faults`` runs the three faults in one
child process on four host devices. A sound run reads ``correct`` true; a state left unchanged
and the halo exchange left out read false."""
import dataclasses

from bench.tests import bench_faults


def test_four_device_faults_run_in_a_child_and_catch_the_halo():
    c = bench_faults.tiny("resnet50.b128")
    cell = dataclasses.replace(
        c, name="resnet50.ds4", chips=4,
        traffic=dict(c.traffic, strategy="ds", mesh={"data": 1, "model": 4}))
    r = bench_faults.run(cell, ["sound", "unchanged", "halo"])
    assert {f: v["devices"] for f, v in r.items()} == {
        "sound": 4, "unchanged": 4, "halo": 4}
    assert r["sound"]["correct"] is True, r["sound"]["checks"]
    assert r["unchanged"]["correct"] is False, r["unchanged"]["checks"]
    assert r["halo"]["correct"] is False, r["halo"]["checks"]
