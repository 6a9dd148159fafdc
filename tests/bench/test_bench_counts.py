"""Labels of device operations, read from the HLO text the v5e trace
prints as the event name; and the float64 reference on hand-made
cases."""
import numpy as np
import pytest

from benchtiny import ROOT
from harness import hlo
from harness.reference import Tally, beyond_theta, reference
from harness.registry import Registry

L2 = Registry(ROOT).space("l2")

PAIRWISE = ("%pairwise_sq_dists.1 = f32[512,250368]{1,0:T(8,128)} "
            "custom-call(f32[512,128]{1,0:T(8,128)S(1)} %copy-done, "
            "f32[250368,128]{1,0:T(8,128)} %get-tuple-element.1, "
            "f32[512,1]{1,0:T(8,128)S(1)} %copy.1, f32[1,250368]"
            "{1,0:T(1,128)S(1)} %broadcast_in_dim.5), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={f32[512,128]}")


def test_hlo_names_and_labels():
    assert hlo.op_name(PAIRWISE) == "pairwise_sq_dists.1"
    assert hlo.short(PAIRWISE) == \
        "pairwise_sq_dists.1 f32[512,250368] custom-call"
    tup = "%while.12 = (s32[2,3]{1,0}, pred[4]{0}) while((s32[2,3]{1,0} %a)"
    assert hlo.short(tup) == "while.12 s32[2,3],pred[4] while"
    assert hlo.short("%fusion = f32[1]{0} fusion()") == "fusion f32[1] fusion"


def test_reference_on_hand_made_case():
    X = np.array([[0.0, 0.0], [10.0, 10.0]], np.float32)
    Y = np.array([[0.5, 0.0], [0.0, 2.0], [10.0, 9.0], [3.0, 4.0]],
                 np.float32)
    ref = reference(X, Y, theta=2.5, space=L2, block=3)
    # d(x0,y0)=0.5, d(x0,y1)=2, d(x1,y2)=1 are in; d(x0,y3)=5 is out
    assert ref.truth == {(0, 0), (0, 1), (1, 2)}
    pairs = np.array([[0, 0], [0, 3], [1, 2], [1, 9], [-1, 0]])
    # (0,3) lies beyond θ; (1,9) and (-1,0) name no row
    assert beyond_theta(pairs, X, Y, 2.5, L2) == 3


def test_band_at_theta_is_a_tie():
    X = np.array([[0.0, 0.0]], np.float32)
    Y = np.array([[3.0, 4.0]], np.float32)
    ref = reference(X, Y, theta=5.0, space=L2)
    assert ref.truth == set()
    # a pair exactly at θ is inside the band: emitting it is no fault
    assert beyond_theta(np.array([[0, 0]]), X, Y, 5.0, L2) == 0
    assert L2.GUARD < 1e-5


def test_tally_recall_offband_and_duplicates():
    X = np.array([[0.0, 0.0], [10.0, 10.0]], np.float32)
    Y = np.array([[0.5, 0.0], [0.0, 2.0], [10.0, 9.0], [3.0, 4.0]],
                 np.float32)
    ref = reference(X, Y, theta=2.5, space=L2)
    t = Tally()
    t.add(np.array([[0, 0], [1, 2], [1, 2]]), ref, {0: 0, 1: 1}, X, Y)
    assert (t.found, t.wanted, t.duplicates) == (2, 3, 1)
    assert t.recall == pytest.approx(2 / 3)
    t2 = Tally()
    t2.add(np.array([[0, 0], [0, 1]]), ref, {0: 0}, X[:1], Y)
    t += t2
    assert (t.found, t.wanted, t.answers) == (4, 5, 2)
