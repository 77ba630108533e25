/* LD_PRELOAD syscall-wrapper counter for the serving edge
 * (docs/SERVING.md).
 *
 * The container ships no strace/perf, so the syscall-floor breakdown
 * is measured by interposing the libc wrappers the C serving loop
 * (native/serve.c) goes through: every call bumps a per-symbol
 * counter, and SIGUSR2 dumps the cumulative table to the file named
 * by $WEED_SYSCOUNT_OUT.  The bench snapshots before and after a
 * closed-loop GET window and divides the delta by the request count —
 * an external measurement of syscalls-per-request, not the loop's own
 * bookkeeping.
 *
 * Only wrappers are counted: raw syscall(2) users (futex from the
 * GIL, clock_nanosleep from time.sleep) never enter these PLT stubs,
 * which is exactly right — they are not part of the serving edge.
 *
 *   cc -O2 -shared -fPIC -o syscount.so syscount.c
 *   LD_PRELOAD=./syscount.so WEED_SYSCOUNT_OUT=/tmp/c.txt python ...
 */
#define _GNU_SOURCE
#include <dlfcn.h>
#include <errno.h>
#include <fcntl.h>
#include <signal.h>
#include <stdarg.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/sendfile.h>
#include <sys/socket.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <unistd.h>

/* every wrapper symbol native/serve.c can reach */
#define WEED_COUNTED(X)                                                  \
    X(accept4) X(epoll_wait) X(epoll_ctl) X(recv) X(recvfrom) X(send)    \
    X(sendto) X(sendmsg) X(writev) X(write) X(read) X(sendfile)          \
    X(close) X(fcntl) X(setsockopt) X(dup) X(dup3)

enum {
#define WEED_ENUM(n) CNT_##n,
    WEED_COUNTED(WEED_ENUM)
#undef WEED_ENUM
        CNT_MAX
};

static const char *const weed_names[CNT_MAX] = {
#define WEED_NAME(n) #n,
    WEED_COUNTED(WEED_NAME)
#undef WEED_NAME
};

static unsigned long long weed_counts[CNT_MAX];
static unsigned long long weed_dump_gen;
static const char *weed_out_path;

static int (*real_close)(int);

static void *weed_real(const char *name) {
    void *fn = dlsym(RTLD_NEXT, name);
    if (fn == NULL) abort(); /* libc without the symbol: unusable rig */
    return fn;
}

#define BUMP(n) \
    __atomic_fetch_add(&weed_counts[CNT_##n], 1, __ATOMIC_RELAXED)

/* SIGUSR2: rewrite the dump file with the cumulative table. Only
 * async-signal-safe calls (open/write/close via the saved real
 * pointer so the dump's own close is not counted). */
static void weed_dump(int sig) {
    (void)sig;
    int saved = errno;
    char buf[2048];
    size_t off = 0;
    unsigned long long gen =
        __atomic_add_fetch(&weed_dump_gen, 1, __ATOMIC_RELAXED);
    off += (size_t)snprintf(buf + off, sizeof(buf) - off,
                            "gen %llu\n", gen);
    for (int i = 0; i < CNT_MAX; i++)
        off += (size_t)snprintf(
            buf + off, sizeof(buf) - off, "%s %llu\n", weed_names[i],
            __atomic_load_n(&weed_counts[i], __ATOMIC_RELAXED));
    int fd = open(weed_out_path ? weed_out_path : "/dev/null",
                  O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (fd >= 0) {
        ssize_t n = write(fd, buf, off);
        (void)n;
        if (real_close != NULL)
            real_close(fd);
    }
    errno = saved;
}

__attribute__((constructor)) static void weed_syscount_init(void) {
    weed_out_path = getenv("WEED_SYSCOUNT_OUT");
    real_close = (int (*)(int))weed_real("close");
    struct sigaction sa;
    memset(&sa, 0, sizeof(sa));
    sa.sa_handler = weed_dump;
    sa.sa_flags = SA_RESTART;
    sigaction(SIGUSR2, &sa, NULL);
}

int accept4(int fd, struct sockaddr *addr, socklen_t *len, int flags) {
    static int (*real)(int, struct sockaddr *, socklen_t *, int);
    if (real == NULL) real = weed_real("accept4");
    BUMP(accept4);
    return real(fd, addr, len, flags);
}

int epoll_wait(int epfd, struct epoll_event *ev, int max, int timeout) {
    static int (*real)(int, struct epoll_event *, int, int);
    if (real == NULL) real = weed_real("epoll_wait");
    BUMP(epoll_wait);
    return real(epfd, ev, max, timeout);
}

int epoll_ctl(int epfd, int op, int fd, struct epoll_event *ev) {
    static int (*real)(int, int, int, struct epoll_event *);
    if (real == NULL) real = weed_real("epoll_ctl");
    BUMP(epoll_ctl);
    return real(epfd, op, fd, ev);
}

ssize_t recv(int fd, void *buf, size_t len, int flags) {
    static ssize_t (*real)(int, void *, size_t, int);
    if (real == NULL) real = weed_real("recv");
    BUMP(recv);
    return real(fd, buf, len, flags);
}

ssize_t recvfrom(int fd, void *buf, size_t len, int flags,
                 struct sockaddr *src, socklen_t *slen) {
    static ssize_t (*real)(int, void *, size_t, int, struct sockaddr *,
                           socklen_t *);
    if (real == NULL) real = weed_real("recvfrom");
    BUMP(recvfrom);
    return real(fd, buf, len, flags, src, slen);
}

ssize_t send(int fd, const void *buf, size_t len, int flags) {
    static ssize_t (*real)(int, const void *, size_t, int);
    if (real == NULL) real = weed_real("send");
    BUMP(send);
    return real(fd, buf, len, flags);
}

ssize_t sendto(int fd, const void *buf, size_t len, int flags,
               const struct sockaddr *dst, socklen_t dlen) {
    static ssize_t (*real)(int, const void *, size_t, int,
                           const struct sockaddr *, socklen_t);
    if (real == NULL) real = weed_real("sendto");
    BUMP(sendto);
    return real(fd, buf, len, flags, dst, dlen);
}

ssize_t sendmsg(int fd, const struct msghdr *msg, int flags) {
    static ssize_t (*real)(int, const struct msghdr *, int);
    if (real == NULL) real = weed_real("sendmsg");
    BUMP(sendmsg);
    return real(fd, msg, flags);
}

ssize_t writev(int fd, const struct iovec *iov, int iovcnt) {
    static ssize_t (*real)(int, const struct iovec *, int);
    if (real == NULL) real = weed_real("writev");
    BUMP(writev);
    return real(fd, iov, iovcnt);
}

ssize_t write(int fd, const void *buf, size_t len) {
    static ssize_t (*real)(int, const void *, size_t);
    if (real == NULL) real = weed_real("write");
    BUMP(write);
    return real(fd, buf, len);
}

ssize_t read(int fd, void *buf, size_t len) {
    static ssize_t (*real)(int, void *, size_t);
    if (real == NULL) real = weed_real("read");
    BUMP(read);
    return real(fd, buf, len);
}

ssize_t sendfile(int out_fd, int in_fd, off_t *off, size_t count) {
    static ssize_t (*real)(int, int, off_t *, size_t);
    if (real == NULL) real = weed_real("sendfile");
    BUMP(sendfile);
    return real(out_fd, in_fd, off, count);
}

int close(int fd) {
    if (real_close == NULL)
        real_close = (int (*)(int))weed_real("close");
    BUMP(close);
    return real_close(fd);
}

int fcntl(int fd, int cmd, ...) {
    static int (*real)(int, int, ...);
    if (real == NULL)
        real = (int (*)(int, int, ...))weed_real("fcntl");
    BUMP(fcntl);
    va_list ap;
    va_start(ap, cmd);
    void *arg = va_arg(ap, void *);
    va_end(ap);
    return real(fd, cmd, arg);
}

int setsockopt(int fd, int level, int opt, const void *val, socklen_t len) {
    static int (*real)(int, int, int, const void *, socklen_t);
    if (real == NULL) real = weed_real("setsockopt");
    BUMP(setsockopt);
    return real(fd, level, opt, val, len);
}

int dup(int fd) {
    static int (*real)(int);
    if (real == NULL) real = weed_real("dup");
    BUMP(dup);
    return real(fd);
}

int dup3(int oldfd, int newfd, int flags) {
    static int (*real)(int, int, int);
    if (real == NULL) real = weed_real("dup3");
    BUMP(dup3);
    return real(oldfd, newfd, flags);
}
