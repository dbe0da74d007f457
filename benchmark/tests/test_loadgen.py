"""The general generator's orders and the load generator's closes."""
import loadgen
import traffic


def test_rotate_begins_connection_i_at_statement_i():
    assert traffic.orders({"order": "rotate"}, 3, 4, seed=1) == \
        [[0, 1, 2], [1, 2, 0], [2, 0, 1], [0, 1, 2]]


def test_every_seed_sends_the_same_set():
    a = traffic.orders({"order": "shuffle"}, 80, 16, seed=1)
    b = traffic.orders({"order": "shuffle"}, 80, 16, seed=2**31 + 5)
    assert a != b
    assert all(sorted(x) == sorted(y) == list(range(80))
               for x, y in zip(a, b))


def test_equal_rounds_ends_every_connection_on_as_many_passes():
    rounds = loadgen.EqualRounds(3)
    assert all(rounds.begin(i, 0, False) for i in range(3))
    assert rounds.begin(0, 1, False)        # 0 is in its second pass
    # time is up when 1 ends its first: 0 has begun two, so two it is
    assert rounds.begin(1, 1, True)
    assert rounds.begin(2, 1, True)
    assert not rounds.begin(0, 2, True)
    # once the number is fixed it holds whatever a connection's clock says
    assert not rounds.begin(1, 2, False)
    assert not rounds.begin(2, 2, True)


def test_equal_rounds_stops_the_first_when_none_is_ahead():
    rounds = loadgen.EqualRounds(2)
    assert rounds.begin(0, 0, False) and rounds.begin(1, 0, False)
    assert not rounds.begin(0, 1, True)
    assert not rounds.begin(1, 1, False)
