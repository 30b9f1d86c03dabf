"""step_stall_share: with periods p from one ``lm.train_step`` span's start
to the next one's and m their median, 100 x sum(max(0, p - 1.25 m)) / sum(p).
A sound run reads about 0; one call of 2.0 s in a window of 15 reads about
10. The period in which a traced run's capture was written is left out. No
account on the spans, or under 8 calls: nothing."""
import host_account


def read(run):
    return host_account.stall_share(run)
